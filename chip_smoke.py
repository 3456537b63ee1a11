"""Chip smoke test: the compressed-serving path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # four chips: phase a, then mesh serving

Phases, in order; each prints one line with its seconds (compile included)
and what it checked, and any failure exits non-zero:

  a. device    the platform must be ``tpu`` — there is no CPU fallback.
  b. dense     olmo-1b at its published config (bf16, weights from the seed)
               served through ``ServingEngine`` + ``Scheduler`` on the paged
               KV pool; one request's logits after prefill plus cached decode
               against an f32 full-sequence forward.
  c. kernels   the compiled LCC kernels (``lcc_chain_matmul``,
               ``lcc_group_matmul``) at olmo-1b's attn.q, ffn.up and ffn.down
               shapes on seeded synthetic chains, against ``kernels/ref.py``.
  d. compressed an olmo-1b-family artifact compressed inside the run (at a
               width that compresses within minutes), served on the compiled
               kernels; logits against its dense-effective weights.
  mesh (``--chips 4`` only): yi-9b at its published config on a 1x4
               ("data", "model") mesh, then yi-9b cut to 4 layers on one chip
               and on the mesh, logits compared.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything is built from the seed inside the run; the persistent compile
cache (``repro.launch.compile_cache``) is the only state it reuses.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if not (SRC / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro sources at {SRC}; run it from a checkout")
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.lcc import LCCChain, LCCDecomposition, LCCFactor  # noqa: E402
from repro.core.lcc import plan_col_slices  # noqa: E402
from repro.data.synthetic import MarkovLM  # noqa: E402
from repro.kernels import dispatch, ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import api, transformer  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.serving.scheduler import Scheduler  # noqa: E402

# Served logits (bf16 weights and activations, f32 accumulation) against an
# f32 reference: bf16 keeps 8 significant bits (unit roundoff 2^-9 ~ 2e-3),
# every layer rounds its weights, activations and residual stream, and the
# errors of 16 layers and the tied-embedding head add up to a few 1e-2 of the
# logits' scale at most.  A wrong cache position, mask or kernel moves the
# logits by O(1) of their scale, far past this bound.
BF16_LOGIT_TOL = 5e-2
# Compiled LCC kernel against the ref oracle: both sum exact signed powers
# of two times f32 inputs in f32 and differ only in summation order; 2^-24
# per add over <= 1e3 slice sums stays under 1e-4 of the output scale, while
# a wrong index or coefficient moves an output by O(1) of it.
KERNEL_TOL = 1e-4

# phase b: olmo-1b traffic (prompt lengths span the 32..256 prefill buckets)
DENSE_PROMPT_LENS = (32, 40, 64, 96, 128, 192, 256)
DENSE_MAX_NEW = (16, 32, 24, 16, 32, 24, 16)
# phase d: olmo-1b family cut to the widest layer that compresses within ~3
# minutes on the chip host (head_dim 128 and d_ff = 4 d_model kept; one
# layer: compression time is linear in depth and steep in width)
COMPRESSED_WIDTH = dict(d_model=384, n_heads=3, n_kv_heads=3, d_ff=1536,
                        n_layers=1)
# In-process compression: on the 13-core chip host a 12-worker pool was 4.6x
# slower at d_model 128 (every worker's BLAS spawns a thread per core) and
# its transient memory outgrew the host's 40 GiB at d_model 384.
COMPRESS_WORKERS = 1
# logit probes: k greedy decode steps after the prompt
PROBE_STEPS = 8


class SmokeError(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def make_prompts(vocab: int, lens, seed: int) -> list[list[int]]:
    lm = MarkovLM(vocab=vocab, k=8, seed=seed)
    return [lm.sample(1, n, seed=seed + 1 + i)[0, :n].tolist()
            for i, n in enumerate(lens)]


def serve(eng: ServingEngine, prompts, max_news) -> list:
    """Serve every prompt through the scheduler; every request must finish
    without error and with its full token budget."""
    sched = Scheduler(eng)
    rids = [sched.enqueue(p, max_new=m) for p, m in zip(prompts, max_news)]
    sched.run()
    res = [sched.take_result(r) for r in rids]
    errors = [(i, r.error) for i, r in enumerate(res) if r.error]
    check(not errors, f"requests failed: {errors}")
    got = [len(r.tokens) - r.prompt_len for r in res]
    check(got == list(max_news), f"generated {got}, expected {list(max_news)}")
    return res


def cached_decode_logits(eng: ServingEngine, prompt, n_steps: int):
    """Logits of the token after ``prompt`` + ``n_steps`` greedy tokens, read
    from the engine's own (paged) cache: the prompt is prefilled by
    ``submit``, ``n_steps`` fused steps decode, and one more ``api.decode``
    through the engine's executor evaluates the next position.  Returns
    (logits [V] f32, the request's tokens)."""
    rid = eng.submit(prompt, max_new=n_steps + 1)
    slot = next(s for s, r in eng.slot_req.items() if r == rid)
    for _ in range(n_steps):
        eng.step()
    toks = eng.results[rid].tokens
    check(len(toks) == len(prompt) + n_steps, "probe request stopped early")
    w = len(toks) - 1  # cache index the probe step writes
    if eng.pool is not None:
        # the engine allocates a step's block right before the step; the
        # probe writes into the block its previous step already holds
        check(w % eng.pool.block_size != 0,
              "probe would write into an unallocated KV block")
    tok = np.zeros((eng.n_slots, 1), np.int32)
    tok[slot, 0] = toks[-1]
    pos = np.full(eng.n_slots, -1, np.int32)
    pos[slot] = w
    cfg, ex = eng.cfg, eng.executor
    step = jax.jit(lambda p, s, t, q: api.decode(p, cfg, s, t, q, executor=ex))
    logits, _ = step(eng.params, eng.state, jnp.asarray(tok), jnp.asarray(pos))
    out = np.asarray(logits[slot], np.float32)
    eng.cancel(rid)
    return out, list(toks)


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """Last-position logits of a full-sequence forward in f32 at highest
    matmul precision — the plain reference the served logits answer to."""
    cfg32 = replace(cfg, param_dtype="float32", compute_dtype="float32")
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    toks = jnp.asarray(np.asarray(tokens, np.int32)[None])
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: transformer.logits_from_hidden(
            p, cfg32, api.prefill(p, cfg32, {"tokens": t})[0][:, -1]))
        out = np.asarray(fwd(p32, toks)[0], np.float32)
    del p32
    return out


# --------------------------------------------------------------- phase b


def phase_dense(cfg, *, seed: int, n_slots: int = 4, max_len: int = 512,
                prompt_lens=DENSE_PROMPT_LENS, max_news=DENSE_MAX_NEW,
                probe_steps: int = PROBE_STEPS) -> str:
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    eng = ServingEngine(params, cfg, n_slots=n_slots, max_len=max_len,
                        seed=seed)
    prompts = make_prompts(cfg.vocab, prompt_lens, seed)
    res = serve(eng, prompts, max_news)
    got, toks = cached_decode_logits(eng, prompts[0], probe_steps)
    n = min(len(toks), len(res[0].tokens))
    check(toks[:n] == res[0].tokens[:n],
          "probe tokens differ from the served request's greedy tokens")
    err = rel_l2(got, reference_logits(params, cfg, toks))
    check(err <= BF16_LOGIT_TOL,
          f"logits rel-L2 {err:.3g} > {BF16_LOGIT_TOL} against f32 reference")
    return (f"{cfg.name} L{cfg.n_layers} d{cfg.d_model} ff{cfg.d_ff} "
            f"V{cfg.vocab} {cfg.compute_dtype}: {len(res)}/{len(res)} "
            f"requests ok ({sum(max_news)} tokens, {eng.step_dispatches} "
            f"steps), probe logits rel-L2 {err:.3g} <= {BF16_LOGIT_TOL}")


# --------------------------------------------------------------- phase c


def synthetic_decomposition(rng, n: int, k: int, *, max_factors: int = 4,
                            s_terms: int = 2) -> LCCDecomposition:
    """A seeded FP decomposition of an [n, k] site: the column slices the
    compressor would cut, each a chain of 1..max_factors factors whose rows
    sum ``s_terms`` signed powers of two (|coefficient| <= 1/2, so values
    stay O(1) through the chain)."""
    slices, chains = plan_col_slices(n, k), []
    for c0, c1 in slices:
        width, factors = c1 - c0, []
        for p in range(int(rng.integers(1, max_factors + 1))):
            in_dim = width if p == 0 else n
            factors.append(LCCFactor(
                idx=rng.integers(0, in_dim, (n, s_terms)).astype(np.int32),
                exp=rng.integers(-3, 0, (n, s_terms)).astype(np.int8),
                sign=rng.choice([-1, 1], (n, s_terms)).astype(np.int8),
                in_dim=in_dim))
        chains.append(LCCChain(factors=factors, in_dim=width))
    return LCCDecomposition(shape=(n, k), col_slices=slices, slices=chains,
                            algorithm="fp", target_snr_db=0.0)


def reference_apply(packed, x) -> np.ndarray:
    """sum_e ref.lcc_chain_apply_ref(chain_e, x_e) at highest precision."""
    n, b = packed.out_dim, x.shape[1]
    slabs = jnp.stack([jnp.pad(x[c0:c1], ((0, packed.d_pad - (c1 - c0)),
                                          (0, 0)))
                       for c0, c1 in packed.col_slices])

    def one(acc, a):
        idx, exp, sign, xe = a
        y = ref.lcc_chain_apply_ref(
            [(idx[p], exp[p], sign[p]) for p in range(idx.shape[0])], xe)
        return acc + y, None

    with jax.default_matmul_precision("highest"):
        y, _ = jax.jit(lambda *a: jax.lax.scan(
            one, jnp.zeros((packed.idx.shape[2], b), jnp.float32), a))(
            packed.idx, packed.exp, packed.sign, slabs)
    return np.asarray(y[:n])


def kernel_error(got, want) -> float:
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want))
                 / max(np.max(np.abs(want)), 1e-30))


def phase_kernels(sites, *, seed: int, batch: int = 8) -> str:
    """``sites``: (name, n_out, k_in, group size) — the group kernel runs
    that many same-shape sites in one launch, as a fused region does."""
    rng = np.random.default_rng(seed)
    parts = []
    for name, n, k, g in sites:
        decs = [synthetic_decomposition(rng, n, k) for _ in range(g)]
        packed = [ops.pack_decomposition(d) for d in decs]
        xs = [jnp.asarray(rng.standard_normal((k, batch)), jnp.float32)
              for _ in range(g)]
        wants = [reference_apply(p, x) for p, x in zip(packed, xs)]
        chain = ops.apply_packed_decomposition(packed[0], xs[0])
        err_c = kernel_error(chain, wants[0])
        check(err_c <= KERNEL_TOL,
              f"{name} lcc_chain_matmul error {err_c:.3g} > {KERNEL_TOL}")
        group = ops.apply_packed_group(ops.pack_group(packed), xs)
        err_g = max(kernel_error(y, w) for y, w in zip(group, wants))
        check(err_g <= KERNEL_TOL,
              f"{name} lcc_group_matmul error {err_g:.3g} > {KERNEL_TOL}")
        e, p, n_pad, s = packed[0].idx.shape
        parts.append(f"{name} {k}->{n} [E{e} P{p} N{n_pad} S{s}] chain "
                     f"{err_c:.2g} group(G{g}) {err_g:.2g}")
    return "; ".join(parts) + f" (max-abs error / output scale <= {KERNEL_TOL})"


OLMO_SITES = (("attn.q", 2048, 2048, 3), ("ffn.up", 8192, 2048, 2),
              ("ffn.down", 2048, 8192, 1))


# --------------------------------------------------------------- phase d


def phase_compressed(cfg, *, seed: int, n_workers: int, n_slots: int = 4,
                     max_len: int = 128, prompt_lens=(32, 48, 64),
                     max_news=(8, 8, 8), probe_steps: int = PROBE_STEPS
                     ) -> str:
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    t0 = time.perf_counter()
    art = api.compress_model(params, cfg, n_workers=n_workers)
    compress_s = time.perf_counter() - t0
    eng = ServingEngine(artifact=art, use_kernel=True, n_slots=n_slots,
                        max_len=max_len, seed=seed)
    check(eng.executor is not None, "artifact has no kernel-routable sites")
    serve(eng, make_prompts(cfg.vocab, prompt_lens, seed), max_news)
    ex = eng.executor
    missing = sorted(set(art.records) - ex.routed)
    check(not missing, f"compressed sites not routed through kernels: "
                       f"{missing[:8]}")
    launches = eng.pallas_launches_per_step
    check(launches > 0, "decode step emitted no Pallas launch")
    fallbacks = eng.plan_stats()["fallbacks"]
    prompt = make_prompts(cfg.vocab, prompt_lens[:1], seed)[0]
    got, toks = cached_decode_logits(eng, prompt, probe_steps)
    err = rel_l2(got, reference_logits(art.params, cfg, toks))
    check(err <= BF16_LOGIT_TOL,
          f"logits rel-L2 {err:.3g} > {BF16_LOGIT_TOL} against the "
          "dense-effective f32 reference")
    return (f"width d{cfg.d_model} ff{cfg.d_ff} L{cfg.n_layers} compressed in "
            f"{compress_s:.1f}s (n_workers={n_workers}); {len(art.records)} "
            f"sites, {len(ex.routed & set(art.records))} routed, "
            f"{launches} launches/step, plan fallbacks {fallbacks}; probe "
            f"logits rel-L2 {err:.3g} <= {BF16_LOGIT_TOL}")


# ------------------------------------------------------------ mesh phase


def phase_mesh(full_cfg, cut_cfg, mesh, *, seed: int, n_slots: int = 4,
               max_len: int = 256, prompt_lens=(32, 64, 128),
               max_news=(8, 8, 8), probe_steps: int = PROBE_STEPS) -> str:
    """``full_cfg`` served on ``mesh``; ``cut_cfg`` served on one device and
    on ``mesh`` in this process, probe logits compared."""
    from repro.distributed import sharding

    params = sharding.init_sharded(
        lambda: api.init_params(jax.random.PRNGKey(seed), full_cfg), mesh,
        sharding.params_pspecs)
    eng = ServingEngine(params, full_cfg, n_slots=n_slots, max_len=max_len,
                        seed=seed, mesh=mesh)
    serve(eng, make_prompts(full_cfg.vocab, prompt_lens, seed), max_news)
    del eng, params

    params = api.init_params(jax.random.PRNGKey(seed), cut_cfg)
    prompt = make_prompts(cut_cfg.vocab, prompt_lens[:1], seed)[0]
    one = ServingEngine(params, cut_cfg, n_slots=n_slots, max_len=max_len,
                        seed=seed)
    want, toks_one = cached_decode_logits(one, prompt, probe_steps)
    del one
    sharded = ServingEngine(params, cut_cfg, n_slots=n_slots,
                            max_len=max_len, seed=seed, mesh=mesh)
    got, _ = cached_decode_logits(sharded, toks_one[:len(prompt)],
                                  probe_steps)
    err = rel_l2(got, want)
    check(err <= BF16_LOGIT_TOL,
          f"mesh vs one-device logits rel-L2 {err:.3g} > {BF16_LOGIT_TOL}")
    shape = "x".join(str(v) for v in mesh.shape.values())
    return (f"{full_cfg.name} L{full_cfg.n_layers} on mesh {shape}: "
            f"{len(prompt_lens)} requests ok; L{cut_cfg.n_layers} one-device "
            f"vs mesh probe logits rel-L2 {err:.3g} <= {BF16_LOGIT_TOL}")


# ------------------------------------------------------------------ main


def run_phase(tag: str, title: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    print(f"[{tag}] {title}: pass in {time.perf_counter() - t0:.1f}s — {out}",
          flush=True)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    t0 = time.perf_counter()
    devs = jax.devices()
    dev = devs[0]
    ok = dev.platform == "tpu" and len(devs) >= args.chips
    print(f"[a] device: {'pass' if ok else 'FAIL'} in "
          f"{time.perf_counter() - t0:.1f}s — platform={dev.platform} "
          f"kind={dev.device_kind!r} count={len(devs)} (compile cache "
          f"{cache})", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: needs {args.chips} TPU device(s), found "
                 f"{len(devs)} {dev.platform!r}; nothing to check")

    if args.chips == 4:
        from repro import compat

        yi = get_arch("yi-9b")
        mesh = compat.make_mesh((1, 4), ("data", "model"), devices=devs[:4])
        run_phase("mesh", "yi-9b sharded serving", phase_mesh, yi,
                  replace(yi, n_layers=4), mesh, seed=args.seed)
    else:
        run_phase("b", "dense olmo-1b at published config", phase_dense,
                  get_arch("olmo-1b"), seed=args.seed)
        check(dispatch.resolve_interpret(None) is False,
              "kernels would run in the Pallas interpreter")
        run_phase("c", "compiled LCC kernels at olmo-1b site shapes",
                  phase_kernels, OLMO_SITES, seed=args.seed)
        run_phase("d", "compressed serving on compiled kernels",
                  phase_compressed,
                  replace(get_arch("olmo-1b"), **COMPRESSED_WIDTH),
                  seed=args.seed, n_workers=COMPRESS_WORKERS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
