"""Distribution: sharding policy properties + multi-device semantics.

Multi-device tests run in a SUBPROCESS with a small host-device count so the
main test process keeps the real single-device view (the dry-run is the only
place that sees 512 fake devices).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_arch, reduced_config
from repro.distributed.elastic import HeartbeatMonitor, plan_for_devices
from repro.models import api

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    # virtual host devices: these children run on the CPU backend by design
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------------ policy


def test_param_pspecs_divisibility():
    """Every assigned spec axis must divide the tensor dim (else compile fails)."""
    import jax
    from jax.sharding import Mesh
    from repro.distributed.sharding import params_pspecs
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    for arch in ("olmo-1b", "mixtral-8x22b", "whisper-small", "rwkv6-1.6b"):
        cfg = get_arch(arch)
        params = api.abstract_params(cfg)
        specs = params_pspecs(params, mesh)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda s: hasattr(s, "index"))
        assert len(flat_p) == len(flat_s)
        for p, s in zip(flat_p, flat_s):
            for dim, ax in zip(p.shape, tuple(s)):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % size == 0, (p.shape, tuple(s), arch)


def test_plan_for_devices():
    assert plan_for_devices(512).shape == (2, 16, 16)
    assert plan_for_devices(256).shape == (16, 16)
    assert plan_for_devices(240).shape == (15, 16)  # lost a host: shrink data axis


def test_heartbeat_monitor_flags_straggler():
    mon = HeartbeatMonitor(n_pods=2, timeout_s=100, straggler_factor=3.0)
    t = 0.0
    for step in range(8):  # pod0 1s/step, pod1 5s/step (straggler)
        mon.beat(0, t + step * 1.0)
        mon.beat(1, t + step * 5.0)
    failed = mon.failed_pods(now=40.0)
    assert 1 in failed
    assert mon.surviving_device_count(512, failed) == 256


def test_peaks_keyed_by_device_kind():
    """Roofline peaks come from the chip's ``device_kind``; a kind without
    published peaks is an error, never a default."""
    from repro.launch.mesh import PRODUCTION_KIND, peaks

    assert peaks(PRODUCTION_KIND).flops_bf16 == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


# --------------------------------------------------------------- semantics


def test_compressed_psum_error_feedback_subprocess():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro import compat
        from repro.distributed.compress_grads import compressed_psum
        mesh = compat.make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)  # per-pod grads
        e = jnp.zeros_like(g)

        def f(g, e):
            return compressed_psum({"w": g}, {"w": e}, "pod")

        fn = compat.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                              out_specs=(P("pod"), P("pod")), check_vma=False)
        (gh, eh) = fn(g, e)
        true_mean = np.asarray(g).mean(0)
        got = np.asarray(gh["w"][0])
        rel = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
        assert rel < 0.02, rel  # int8 quantization error bound
        # error feedback: residual equals local (v - decoded q)
        assert np.isfinite(np.asarray(eh["w"])).all()
        # second round with error feedback reduces bias on a CONSTANT gradient
        (gh2, eh2) = fn(g, eh["w"][None][0] if False else eh["w"])
        err1 = np.abs(np.asarray(gh["w"][0]) - true_mean).mean()
        err2 = np.abs((np.asarray(gh["w"][0]) + np.asarray(gh2["w"][0])) / 2
                      - true_mean).mean()
        assert err2 <= err1 + 1e-6
        print("OK")
    """)
    assert "OK" in out


def test_pjit_train_step_multidevice_subprocess():
    """End-to-end sharded train step on an 8-device host mesh."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_arch, reduced_config
        from repro.distributed import sharding
        from repro.distributed.act_shard import mesh_context
        from repro.optim.optimizers import adamw
        from repro.training.trainer import init_train_state, make_train_step
        from repro import compat
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        cfg = reduced_config(get_arch("olmo-1b"), d_model=64, d_ff=128, vocab=256,
                             n_heads=4, n_kv_heads=4, head_dim=16)
        opt = adamw()
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        with mesh, mesh_context(mesh):
            state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
            pspecs = sharding.params_pspecs(state, mesh)
            state = jax.device_put(state, sharding.named(mesh, pspecs))
            step = jax.jit(make_train_step(cfg, opt, lr=1e-3))
            losses = []
            for i in range(5):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses  # actually optimizes, sharded
        print("OK", losses[0], losses[-1])
    """)
    assert "OK" in out


def test_gpipe_pipeline_subprocess():
    """GPipe stage runner == running layers sequentially."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import gpipe_forward, split_stages
        from repro import compat
        mesh = compat.make_mesh((4,), ("pipe",))
        rng = np.random.default_rng(0)
        L, D = 8, 16
        w = jnp.asarray(rng.standard_normal((L, D, D)) * 0.3, jnp.float32)

        def stage_fn(ws, x):  # ws [L/S, D, D]
            def body(x, wi):
                return jnp.tanh(x @ wi), None
            x, _ = jax.lax.scan(body, x, ws)
            return x

        x = jnp.asarray(rng.standard_normal((4, 2, D)), jnp.float32)  # [M, mb, D]
        got = gpipe_forward(split_stages(w, 4), x, stage_fn, mesh=mesh)
        want = x
        for i in range(L):
            want = jnp.tanh(want @ w[i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_overlapped_ag_matmul_subprocess():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.overlap import overlapped_ag_matmul
        from repro import compat
        mesh = compat.make_mesh((4,), ("model",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((6, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
        got = overlapped_ag_matmul(x, w, mesh=mesh, axis="model")
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w), rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_serving_decode_subprocess():
    """ServingEngine(mesh=...) on a 2-device CPU mesh: decode-state sharded
    over slots (data) or params tensor-parallel (model), generated tokens
    identical to the single-device engine and logits within 1e-4."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_arch, reduced_config
        from repro.models import api
        from repro.serving.engine import ServingEngine
        assert jax.device_count() == 2
        cfg = reduced_config(get_arch("olmo-1b"))
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 9, 2], [7, 1], [4, 4, 4, 8], [30]]
        ref = ServingEngine(params, cfg, n_slots=4, max_len=64)
        r_ref = ref.generate(prompts, max_new_tokens=6)

        tok = jnp.asarray([[3], [1], [2], [7]], jnp.int32)
        pos = jnp.asarray([2, 1, 3, 0], jnp.int32)
        st0 = api.init_decode_state(cfg, 4, 64, kv_block=16)  # engine default layout
        l_ref, _ = ref._decode(ref.params, st0, tok, pos)

        for axes in (("data", "model"), ("model", "data")):
            mesh = compat.make_mesh((2, 1), axes)
            eng = ServingEngine(params, cfg, n_slots=4, max_len=64, mesh=mesh)
            r = eng.generate(prompts, max_new_tokens=6)
            assert [x.tokens for x in r] == [x.tokens for x in r_ref], axes
            st = jax.device_put(st0, eng._state_sh)
            l_sh, _ = eng._decode(eng.params, st, tok, pos)
            d = float(jnp.abs(l_ref.astype(jnp.float32)
                              - l_sh.astype(jnp.float32)).max())
            assert d <= 1e-4, (axes, d)
            print("mesh", axes[0], "max_diff", d)
        print("OK")
    """, devices=2)
    assert "OK" in out


def test_elastic_remesh_reshard_subprocess():
    """Simulated pod loss: save, rebuild smaller mesh, reshard, keep training."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.elastic import plan_for_devices, reshard_tree
        from jax.sharding import PartitionSpec as P
        # 'cluster' of 8 devices -> lose half -> 4
        plan_big = plan_for_devices(8, model_parallel=2, multi_pod_threshold=8)
        mesh_big = plan_big.build()
        w = jnp.arange(64.0).reshape(8, 8)
        specs = P("data", "model")
        from jax.sharding import NamedSharding
        w_sharded = jax.device_put(w, NamedSharding(mesh_big, specs))
        host = np.asarray(w_sharded)  # checkpoint (host copy)
        plan_small = plan_for_devices(4, model_parallel=2, multi_pod_threshold=8)
        mesh_small = plan_small.build(jax.devices()[:4])
        w2 = reshard_tree({"w": host}, mesh_small, {"w": specs})["w"]
        np.testing.assert_array_equal(np.asarray(w2), host)
        assert len(w2.sharding.device_set) == 4
        print("OK", plan_big.shape, plan_small.shape)
    """)
    assert "OK" in out


def test_mesh_sharded_compressed_decode_subprocess():
    """Compressed artifact under a 2-device mesh decodes through the
    per-region LCC launches, with tokens identical and logits within 1e-4 of
    the single-device engine, on both axis orders."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat, core
        from repro.configs import get_arch, reduced_config
        from repro.models import api
        from repro.serving.engine import ServingEngine
        assert jax.device_count() == 2
        cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                             n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                             n_layers=2)
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        comp = core.CompressionConfig(algorithm="fp", weight_sharing=True,
                                      max_share_rel_err=0.06)
        art = api.compress_model(params, cfg, comp)
        prompts = [[5, 9, 2], [7, 1], [4, 4, 4, 8], [30]]
        ref = ServingEngine(artifact=art, n_slots=4, max_len=32)
        r_ref = ref.generate(prompts, max_new_tokens=6)
        n_launch = ref.pallas_launches_per_step
        assert n_launch > 0

        tok = jnp.asarray([[3], [1], [2], [7]], jnp.int32)
        pos = jnp.asarray([2, 1, 3, 0], jnp.int32)
        st0 = api.init_decode_state(cfg, 4, 32, kv_block=16)
        l_ref, _ = ref._decode(ref.params, st0, tok, pos)

        for axes in (("data", "model"), ("model", "data")):
            mesh = compat.make_mesh((2, 1), axes)
            eng = ServingEngine(artifact=art, n_slots=4, max_len=32,
                                mesh=mesh)
            r = eng.generate(prompts, max_new_tokens=6)
            assert eng.pallas_launches_per_step == n_launch, axes
            assert [x.tokens for x in r] == [x.tokens for x in r_ref], axes
            stt = jax.device_put(st0, eng._state_sh)
            l_sh, _ = eng._decode(eng.params, stt, tok, pos)
            d = float(jnp.abs(l_ref.astype(jnp.float32)
                              - l_sh.astype(jnp.float32)).max())
            assert d <= 1e-4, (axes, d)
            print("mesh", axes[0], "launches", eng.pallas_launches_per_step,
                  "max_diff", d)
        print("OK")
    """, devices=2)
    assert "OK" in out
