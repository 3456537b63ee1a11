"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 50 --checkpoint-dir /tmp/ckpt --resume
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --devices 8 --mesh 2x4 --grad-compression --elastic-demo

    # the paper's full loop on the MLP (Sec. IV-A): prox-regularized training
    # -> prune-aware budgeted compression -> recovery fine-tune -> fused serve
    PYTHONPATH=src python -m repro.launch.train --arch mlp --prox \
        --lambda 0.1 --epochs 12 --compress-out /tmp/mlp_run --recover 60 \
        --compress-config algorithm=fp prune_tol=-1e-6 weight_sharing=false

Features: any registered arch (--arch), reduced or full config, sharded SPMD
step on an explicit mesh, ProxSGD group-lasso regularization (the paper's
Algorithm-1 step 1) with compression-aware group layouts (--prox derives the
regularized groups from the same adapter sites the compressor slices), async
checkpoint + auto-resume, int8 cross-pod gradient compression, an
elastic-restart demo (simulated pod loss -> remesh -> reshard -> continue),
and — for --arch mlp — the training -> compression -> recovery handoff that
closes the paper's Algorithm-1 loop in one command.  On real hardware the
same flags apply; --devices N exists to exercise multi-device semantics on
host platform devices.
"""
import os
import sys

# device count must be pinned before jax initializes (same rule as dryrun.py)
if "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_n}"

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_arch, reduced_config
from repro.data.synthetic import MarkovLM
from repro.distributed import sharding
from repro.distributed.act_shard import mesh_context
from repro.distributed.elastic import plan_for_devices, reshard_tree
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.optimizers import adamw, cosine_warmup, prox_sgd
from repro.obs import MetricsRegistry, dump_metrics, get_global
from repro.training.trainer import (TrainState, init_train_state,
                                    make_train_step, record_step_metrics)


def build_mesh(spec: str | None):
    if not spec:
        return None
    dims = tuple(int(x) for x in spec.split("x"))
    axes = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return compat.make_mesh(dims, axes)


def mlp_main(args) -> None:
    """--arch mlp: the paper's Sec. IV-A loop end to end.

    1. (optionally prox-regularized) training on MNIST-scale stroke digits,
       groups derived from the compression adapters so the prox zeroes exactly
       what the compressor slices;
    2. prune-aware budgeted compression via the parallel pipeline (dead input
       columns become 0-add skipped/shrunk slice jobs);
    3. post-compression recovery fine-tuning of the artifact's dense residual
       (frozen chains fixed), written back into every artifact surface;
    4. fused-serving check (whole-chain LCC kernel) + ``train_stats.json``.
    """
    import json

    metrics = MetricsRegistry() if args.metrics_out else None

    from repro.data.mnist_like import train_test
    from repro.data.synthetic import batches
    from repro.models import api
    from repro.models.mlp import (MLPConfig, init_mlp, mlp_accuracy,
                                  mlp_forward_compressed, mlp_loss)
    from repro.optim.optimizers import prox_sgd, step_decay
    from repro.training import regularize

    batch = 128 if args.batch is None else args.batch
    lr0 = 0.08 if args.lr is None else args.lr
    cfg = MLPConfig(hidden=args.hidden)
    (xs, ys), (xte, yte) = train_test(args.train_n, args.test_n, seed=args.seed)
    xte_j, yte_j = jnp.asarray(xte), jnp.asarray(yte)
    params = init_mlp(jax.random.PRNGKey(args.seed), hidden=cfg.hidden)

    specs = regularize.site_group_specs(params, cfg, args.lam,
                                        include=args.prox_include) \
        if args.prox else ()
    opt = prox_sgd(momentum=0.9, specs=specs)
    state = opt.init(params)
    lr = step_decay(lr0, 0.95, 3)
    grad = jax.jit(jax.grad(mlp_loss))
    upd = jax.jit(lambda g, s, p, l: opt.update(g, s, p, l))
    t0 = time.time()
    for ep in range(args.epochs):
        for xb, yb in batches(xs, ys, batch, seed=ep):
            g = grad(params, jnp.asarray(xb), jnp.asarray(yb))
            params, state = upd(g, state, params, lr(ep))
        if specs and (ep % 3 == 0 or ep == args.epochs - 1):
            rep = regularize.sparsity_report(params, specs)
            print(f"epoch {ep:3d}  dead groups "
                  f"{regularize.dead_group_fraction(rep):.1%}  penalty "
                  f"{sum(float(v['penalty']) for v in rep.values()):.3f}",
                  flush=True)
    acc = float(mlp_accuracy(params, xte_j, yte_j))
    if metrics is not None:
        metrics.gauge("train_accuracy", "held-out accuracy by stage",
                      labels=("stage",)).set(acc, stage="dense")
    stats = {"arch": "mlp", "hidden": cfg.hidden, "prox": bool(args.prox),
             "lam": args.lam, "epochs": args.epochs, "batch": batch,
             "train_wall_s": round(time.time() - t0, 2),
             "accuracy": {"dense": acc}}
    if specs:
        rep = regularize.sparsity_report(params, specs)
        stats["dead_group_fraction"] = round(
            regularize.dead_group_fraction(rep), 4)
        stats["sparsity"] = {k: {kk: float(vv) for kk, vv in v.items()}
                             for k, v in rep.items()}
    print(f"train: accuracy {acc:.3f} in {stats['train_wall_s']}s"
          + (f", dead groups {stats['dead_group_fraction']:.1%}"
             if specs else ""))

    if not args.compress_out:
        if args.metrics_out:
            dump_metrics(args.metrics_out, [get_global(), metrics])
            print(f"wrote {args.metrics_out}")
        return

    # ---- handoff to the compression pipeline (launch/compress layout) ----
    from repro.launch.compress import parse_compression

    compression = parse_compression(args.compress_config)
    chatty = {"plan", "skip", "unit_done", "budget", "resume"}

    def progress(ev):
        if ev.kind in chatty:
            print(f"[{ev.kind}] {ev}", flush=True)

    t0 = time.time()
    art = api.compress_model(
        params, cfg, compression, include=args.include,
        n_workers=args.workers, budget_adds=args.budget,
        cache_dir=os.path.join(args.compress_out, "cache"),
        run_dir=os.path.join(args.compress_out, "run"),
        progress=progress, metrics=metrics)
    ps = art.pipeline_stats
    stats["pipeline"] = {k: int(ps.get(k, 0)) for k in
                         ("units", "jobs", "dead_groups", "skipped_jobs",
                          "shrunk_jobs", "cache_hits", "cache_misses")}
    stats["adds"] = {"baseline": int(art.report.total_baseline()),
                     "lcc": int(art.report.total_stage("lcc"))}
    stats["compress_wall_s"] = round(time.time() - t0, 2)
    acc_c = float(mlp_accuracy(art.params, xte_j, yte_j))
    stats["accuracy"]["compressed"] = acc_c
    if metrics is not None:
        metrics.gauge("train_accuracy", "held-out accuracy by stage",
                      labels=("stage",)).set(acc_c, stage="compressed")
    print(f"compress: adds {stats['adds']['baseline']} -> "
          f"{stats['adds']['lcc']} (dead groups {ps['dead_groups']}, "
          f"skipped {ps['skipped_jobs']} jobs, shrunk {ps['shrunk_jobs']}); "
          f"accuracy {acc_c:.3f}")

    if args.recover > 0:
        from repro.training.recover import recover_artifact

        def loss_fn(p, b):
            return mlp_loss(p, b[0], b[1])

        def rec_batches():
            n, ep = 0, 0
            while n < args.recover:
                for xb, yb in batches(xs, ys, batch, seed=1000 + ep):
                    if n >= args.recover:
                        return
                    yield jnp.asarray(xb), jnp.asarray(yb)
                    n += 1
                ep += 1

        t0 = time.time()
        res = recover_artifact(art, loss_fn, rec_batches(),
                               lr=args.recover_lr,
                               residual_frac=args.residual_frac,
                               progress=lambda m: print(f"[recover] {m}",
                                                        flush=True))
        acc_r = float(mlp_accuracy(art.params, xte_j, yte_j))
        residual = sum(u.get("recover_adds", 0) for u in res["units"].values())
        stats["accuracy"]["recovered"] = acc_r
        stats["adds"]["recover_residual"] = int(residual)
        stats["adds"]["total_with_recover"] = stats["adds"]["lcc"] + int(residual)
        stats["recover"] = {"steps": len(res["losses"]),
                            "loss_first": round(res["losses"][0], 5),
                            "loss_last": round(res["losses"][-1], 5),
                            "units": res["units"],
                            "wall_s": round(time.time() - t0, 2)}
        print(f"recover: loss {stats['recover']['loss_first']:.4f} -> "
              f"{stats['recover']['loss_last']:.4f} over "
              f"{len(res['losses'])} steps; accuracy {acc_r:.3f} "
              f"(+{residual} residual adds)")

    # fused-serving check: fc1 through the packed whole-chain LCC kernel
    pk = art.packed.get("fc1")
    if pk is not None:
        logits = mlp_forward_compressed(art.params, pk, xte_j[:256])
        acc_f = float((jnp.argmax(logits, -1) == yte_j[:256]).mean())
        stats["accuracy"]["fused"] = acc_f
        print(f"serve: fused fc1 kernel accuracy {acc_f:.3f} (256 samples)")

    art.save(os.path.join(args.compress_out, "artifact"))
    with open(os.path.join(args.compress_out, "train_stats.json"), "w") as f:
        json.dump(stats, f, indent=2)
        f.write("\n")
    print(f"artifact -> {os.path.join(args.compress_out, 'artifact')}")
    if args.metrics_out:
        dump_metrics(args.metrics_out, [get_global(), metrics])
        print(f"wrote {args.metrics_out}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=None,
                    help="default: 8 (LM), 128 (mlp)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None,
                    help="default: 3e-3 (LM), 0.08 (mlp)")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 or 2x2x2")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--group-lasso", type=float, default=0.0,
                    help="legacy: lambda for ProxSGD on FFN input columns "
                         "(substring spec; prefer --prox)")
    ap.add_argument("--prox", action="store_true",
                    help="ProxSGD with group layouts derived from the "
                         "compression-adapter sites (paper eq. 7/11)")
    ap.add_argument("--lambda", dest="lam", type=float, default=0.1,
                    help="group-lasso strength for --prox")
    ap.add_argument("--prox-include", default=None,
                    help="site-name prefix filter for --prox (e.g. 'fc1')")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--elastic-demo", action="store_true",
                    help="simulate losing half the devices mid-run and recover")
    # --arch mlp: the full train -> compress -> recover -> serve loop
    ap.add_argument("--epochs", type=int, default=12, help="mlp: train epochs")
    ap.add_argument("--hidden", type=int, default=300, help="mlp: hidden width")
    ap.add_argument("--train-n", type=int, default=4000,
                    help="mlp: training examples (mnist_like)")
    ap.add_argument("--test-n", type=int, default=1000,
                    help="mlp: held-out examples")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress-out", default=None,
                    help="mlp: run dir; triggers the compression handoff")
    ap.add_argument("--compress-config", nargs="*", default=[],
                    metavar="KEY=VAL",
                    help="mlp: CompressionConfig overrides (launch.compress)")
    ap.add_argument("--budget", type=int, default=None,
                    help="mlp: global adds budget (allocator)")
    ap.add_argument("--workers", type=int, default=1,
                    help="mlp: pipeline worker processes")
    ap.add_argument("--include", default=None,
                    help="mlp: compression unit-name prefix filter")
    ap.add_argument("--recover", type=int, default=0,
                    help="mlp: post-compression recovery fine-tune steps")
    ap.add_argument("--recover-lr", type=float, default=2e-3)
    ap.add_argument("--residual-frac", type=float, default=0.15,
                    help="recovery residual adds budget as a fraction of the "
                         "unit's LCC adds")
    ap.add_argument("--metrics-out", default=None,
                    help="write the run's metrics snapshot as JSON at exit")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "mlp":
        return mlp_main(args)
    args.batch = 8 if args.batch is None else args.batch
    args.lr = 3e-3 if args.lr is None else args.lr

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg, vocab=256)
    mesh = build_mesh(args.mesh)
    if args.grad_compression and (mesh is None or "pod" not in mesh.shape):
        raise SystemExit("--grad-compression needs a mesh with a pod axis (e.g. 2x2x2)")

    prox_specs = None
    if args.prox:
        from repro.models import api
        from repro.training.regularize import site_group_specs

        prox_specs = site_group_specs(api.abstract_params(cfg), cfg, args.lam,
                                      include=args.prox_include)
        opt = prox_sgd(momentum=0.9, specs=prox_specs)
        print(f"[prox] {len(prox_specs)} site-derived group specs "
              f"(lambda {args.lam})")
    elif args.group_lasso > 0:
        opt = prox_sgd(momentum=0.9, prox_spec={"ffn": (args.group_lasso, "columns")})
    else:
        opt = adamw(weight_decay=0.01)
    lr_fn = cosine_warmup(args.lr, warmup=10, total=args.steps)

    lm = MarkovLM(vocab=cfg.vocab, k=8, seed=0)
    metrics = MetricsRegistry() if args.metrics_out else None
    ck = Checkpointer(args.checkpoint_dir, keep=3) if args.checkpoint_dir else None

    def fresh_state():
        return init_train_state(jax.random.PRNGKey(0), cfg, opt,
                                grad_compression=args.grad_compression,
                                prox_specs=prox_specs)

    def place(state, mesh):
        if mesh is None:
            return state
        specs = sharding.params_pspecs(state, mesh)
        return jax.device_put(state, sharding.named(mesh, specs))

    state = fresh_state()
    start_step = 0
    if ck and args.resume:
        s, restored = ck.restore_latest(state)
        if s is not None:
            state, start_step = restored, s + 1
            print(f"[resume] restored checkpoint step {s}")
    state = place(state, mesh)

    def make_step(mesh):
        step = make_train_step(cfg, opt, lr=args.lr, accum_steps=args.accum_steps,
                               grad_compression=args.grad_compression, mesh=mesh,
                               prox_specs=prox_specs)
        return jax.jit(step)

    step_fn = make_step(mesh)
    ctx = mesh_context(mesh)
    with ctx:
        if mesh is not None:
            jax.sharding.set_mesh(mesh)
        t0 = time.time()
        i = start_step
        while i < args.steps:
            try:
                b = lm.batch(args.batch, args.seq, seed=i)
                state, m = step_fn(state, {k: jnp.asarray(v) for k, v in b.items()})
                if i % 10 == 0 or i == args.steps - 1:
                    tok_s = args.batch * args.seq * max(i - start_step, 1) / (time.time() - t0)
                    # record where the loop already syncs to print, so
                    # telemetry adds no extra device round-trips
                    record_step_metrics(metrics, m, step=i)
                    if metrics is not None:
                        metrics.gauge("train_tok_s",
                                      "training throughput").set(tok_s)
                    prox = (f"  dead {int(m['dead_groups'])}  "
                            f"pen {float(m['prox_penalty']):.2f}"
                            if "dead_groups" in m else "")
                    print(f"step {i:4d}  loss {float(m['loss']):.3f}  "
                          f"gnorm {float(m['grad_norm']):.2f}  tok/s {tok_s:.0f}"
                          + prox, flush=True)
                if ck and i % args.checkpoint_every == 0 and i > start_step:
                    ck.save(i, state)
                if args.elastic_demo and i == args.steps // 2 and mesh is not None \
                        and len(mesh.devices.flatten()) > 2:
                    raise RuntimeError("simulated pod failure")
                i += 1
            except RuntimeError as e:
                if "simulated" not in str(e):
                    raise
                # elastic recovery: shrink mesh, reshard, continue
                survivors = jax.devices()[: max(len(jax.devices()) // 2, 2)]
                plan = plan_for_devices(len(survivors),
                                        model_parallel=min(2, len(survivors)),
                                        multi_pod_threshold=1 << 30)
                new_mesh = plan.build(survivors)
                print(f"[elastic] {e}; remeshing {mesh.shape} -> {new_mesh.shape} "
                      f"and resharding state", flush=True)
                host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
                specs = sharding.params_pspecs(state, new_mesh)
                state = reshard_tree(host, new_mesh, specs)
                mesh = new_mesh
                args.grad_compression = False  # single pod left
                step_fn = make_step(None)
                jax.sharding.set_mesh(mesh)
                from repro.distributed import act_shard
                act_shard.set_mesh(mesh)  # activation constraints follow the new mesh
                i += 1
        if ck:
            ck.save(args.steps - 1, state, blocking=True)
            print(f"[checkpoint] final save at step {args.steps - 1}")
    print(f"done: {args.steps - start_step} steps in {time.time() - t0:.1f}s")
    if args.metrics_out:
        dump_metrics(args.metrics_out, [get_global(), metrics])
        print(f"wrote {args.metrics_out}")


if __name__ == "__main__":
    main()
