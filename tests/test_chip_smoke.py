"""chip_smoke.py's phases rehearsed on the CPU at reduced size.

The script itself refuses to run off a TPU; these tests call its phase
functions directly (the Pallas kernels then run in the interpreter), so the
serving, probing and comparison control flow is exercised before any chip
time is spent.
"""
import json
import os
import subprocess
import sys
import textwrap
from dataclasses import replace

import pytest

from repro.configs import get_arch, reduced_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


def _olmo(**kw):
    return reduced_config(get_arch("olmo-1b"), vocab=512,
                          param_dtype="bfloat16", compute_dtype="bfloat16",
                          **kw)


def test_phase_dense_reduced():
    out = cs.phase_dense(_olmo(n_layers=4), seed=0, n_slots=4, max_len=128,
                         prompt_lens=(20, 24, 40, 64, 33),
                         max_news=(4, 8, 6, 5, 7))
    assert "5/5 requests ok" in out


def test_phase_kernels_reduced():
    out = cs.phase_kernels((("q", 128, 64, 3), ("up", 256, 64, 2),
                            ("down", 64, 256, 1)), seed=0)
    assert out.count("chain") == 3


def test_phase_compressed_reduced():
    cfg = _olmo(d_model=128, n_heads=1, n_kv_heads=1, head_dim=128,
                d_ff=256, n_layers=1)
    out = cs.phase_compressed(cfg, seed=0, n_workers=2, prompt_lens=(20, 24),
                              max_news=(4, 4))
    assert "7 sites, 7 routed" in out


def test_probe_rejects_unallocated_block():
    """The logit probe refuses a write position that opens a new KV block
    (the engine allocates it only inside ``step``)."""
    from repro.models import api
    from repro.serving.engine import ServingEngine

    import jax

    cfg = _olmo(n_layers=1)
    eng = ServingEngine(api.init_params(jax.random.PRNGKey(0), cfg), cfg,
                        n_slots=2, max_len=64)
    with pytest.raises(cs.SmokeError, match="unallocated KV block"):
        cs.cached_decode_logits(eng, list(range(1, 11)), 7)  # writes at 16


def test_phase_mesh_four_virtual_devices():
    code = textwrap.dedent(f"""
        import sys
        from dataclasses import replace
        import jax
        sys.path.insert(0, {REPO!r})
        import chip_smoke as cs
        from repro import compat
        from repro.configs import get_arch, reduced_config
        yi = reduced_config(get_arch("yi-9b"), n_layers=3, vocab=512,
                            n_heads=8, n_kv_heads=4, head_dim=16,
                            param_dtype="bfloat16", compute_dtype="bfloat16")
        mesh = compat.make_mesh((1, 4), ("data", "model"),
                                devices=jax.devices()[:4])
        print(cs.phase_mesh(yi, replace(yi, n_layers=2), mesh, seed=0,
                            max_len=128, prompt_lens=(20, 40, 60),
                            max_news=(4, 4, 4)))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "on mesh 1x4: 3 requests ok" in r.stdout


def test_main_refuses_cpu():
    """No CPU fallback: off a TPU the script fails after phase a and prints
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=REPO)
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    assert lines and lines[-1].startswith("[a] device: FAIL")
    for line in lines:
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_compressed_width_keeps_olmo_head_dim():
    """Phase d cuts olmo-1b in width only: head_dim 128, d_ff = 4 d_model,
    published vocab and dtype."""
    cfg = replace(get_arch("olmo-1b"), **cs.COMPRESSED_WIDTH)
    olmo = get_arch("olmo-1b")
    assert cfg.hd == olmo.hd
    assert cfg.d_ff * olmo.d_model == olmo.d_ff * cfg.d_model
    assert (cfg.vocab, cfg.compute_dtype) == (olmo.vocab, olmo.compute_dtype)
