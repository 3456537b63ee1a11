import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# Placeholder host devices exist ONLY for the dry-run (smoke tests/benches see
# the real single device).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell, two passes (DESIGN.md Sec. 6):
  1. compile pass — full config, scan-over-layers: proves the sharding config
     is coherent (the deliverable) and yields memory_analysis().
  2. cost pass — two reduced-depth *unrolled* lowerings (L1, L2); per-layer
     cost = (c2-c1)/(L2-L1); extrapolated to the full depth.  Yields accurate
     HLO FLOPs / bytes and the collective schedule parsed from the HLO text
     (while bodies are undercounted by cost_analysis, hence the unroll).

Results append to a JSON file consumed by benchmarks/roofline.py and
EXPERIMENTS.md.  Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out dryrun_results.json
"""
import argparse
import json
import re
import time
import traceback
from dataclasses import replace

import jax
import numpy as np

from repro.configs import ARCHS, SHAPE_CELLS, cell_supported, input_specs
from repro.configs.base import ArchConfig, ShapeCell
from repro.distributed import sharding
from repro.distributed.act_shard import mesh_context
from repro.launch.mesh import PRODUCTION_KIND, make_production_mesh, peaks
from repro.models import api
from repro.models import flops as aflops
from repro.optim.optimizers import adamw
from repro.training.trainer import TrainState, make_train_step

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}
_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


# ---------------------------------------------------------------------------
# HLO text parsing: per-device collective link bytes (ring accounting)
# ---------------------------------------------------------------------------


def _shape_bytes(segment: str) -> int:
    tot = 0
    for dt, dims in re.findall(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]",
                               segment):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        tot += n * _DTYPE_BYTES[dt]
    return tot


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, n_devices: int) -> dict:
    """Per-device ICI link bytes by op kind (ring model):
    AG/A2A (n-1)/n * out, RS (n-1) * out, AR 2(n-1)/n * out, CP out."""
    per_kind: dict[str, float] = {k: 0.0 for k in _COLL}
    counts: dict[str, int] = {k: 0 for k in _COLL}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:%[\w.-]+|\w[\w.-]*) = (.*?)\s+(all-gather-start|all-gather|"
                     r"all-reduce-start|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute-start|collective-permute)\(", ls)
        if not m:
            continue
        restype, op = m.groups()
        kind = op.replace("-start", "")
        n = _group_size(ls, n_devices)
        if n <= 1:
            continue
        out_bytes = _shape_bytes(restype)
        if kind == "all-gather":
            link = (n - 1) / n * out_bytes
        elif kind == "all-reduce":
            link = 2 * (n - 1) / n * out_bytes
        elif kind == "reduce-scatter":
            link = (n - 1) * out_bytes
        elif kind == "all-to-all":
            link = (n - 1) / n * out_bytes
        else:  # collective-permute
            link = float(out_bytes)
        per_kind[kind] += link
        counts[kind] += 1
    per_kind["total"] = sum(per_kind[k] for k in _COLL)
    return {"link_bytes": per_kind, "counts": counts}


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def apply_variant(cfg: ArchConfig, variant: str | None) -> ArchConfig:
    """§Perf hillclimb levers, selectable per run (see EXPERIMENTS.md §Perf)."""
    if not variant:
        return cfg
    for v in variant.split("+"):
        if v == "causal_skip":
            cfg = replace(cfg, causal_chunk_skip=True)
        elif v == "remat_off":
            cfg = replace(cfg, remat=False)
        elif v.startswith("qchunk"):
            cfg = replace(cfg, q_chunk=int(v[len("qchunk"):]))
        elif v.startswith("ssmchunk"):
            cfg = replace(cfg, ssm_chunk=int(v[len("ssmchunk"):]))
        elif v == "moe_manual":
            cfg = replace(cfg, moe_manual=True)
        elif v == "ws_decode":
            pass  # handled in build_cell (sharding, not model math)
        else:
            raise ValueError(f"unknown variant {v!r}")
    return cfg


def build_cell(cfg: ArchConfig, cell: ShapeCell, mesh, *, unroll: bool,
               variant: str | None = None):
    """Returns (fn, args, in_shardings)."""
    cfg = apply_variant(cfg, variant)
    ws_decode = variant is not None and "ws_decode" in variant
    specs = input_specs(cfg, cell)
    if cell.kind == "train":
        opt = adamw()
        step = make_train_step(cfg, opt, unroll=unroll)
        state = jax.eval_shape(lambda: TrainState(
            params=api.init_params(jax.random.PRNGKey(0), cfg),
            opt_state=opt.init(api.abstract_params(cfg)),
            step=np.zeros((), np.int32), error_fb=None))
        state_sh = sharding.named(mesh, sharding.params_pspecs(state, mesh))
        batch_sh = sharding.named(mesh, sharding.batch_pspecs(specs, mesh))
        # out_shardings pin the updated state to the input sharding: gradient
        # reduction lowers to reduce-scatter (not full all-reduce) and the
        # optimizer update stays sharded (§Perf iteration 2)
        return step, (state, specs), (state_sh, batch_sh), (state_sh, None)
    params = api.abstract_params(cfg)
    params_sh = sharding.named(
        mesh, sharding.params_pspecs(params, mesh, fsdp=not ws_decode))
    if cell.kind == "prefill":
        def step(params, batch):
            h, _ = api.prefill(params, cfg, batch, unroll=unroll)
            return h

        batch_sh = sharding.named(mesh, sharding.batch_pspecs(specs, mesh))
        h_sh = None  # hidden output: let XLA keep the internal sharding
        return step, (params, specs), (params_sh, batch_sh), h_sh
    # decode
    state = api.abstract_decode_state(cfg, cell)
    state_sh = sharding.named(mesh, sharding.decode_state_pspecs(state, mesh))
    tok_sh = sharding.named(mesh, sharding.batch_pspecs(
        {"token": specs["token"], "pos": specs["pos"]}, mesh))

    def step(params, state, token, pos):
        return api.decode(params, cfg, state, token, pos, unroll=unroll)

    return step, (params, state, specs["token"], specs["pos"]), \
        (params_sh, state_sh, tok_sh["token"], tok_sh["pos"]), (None, state_sh)


def reduce_layers(cfg: ArchConfig, n: int, cell: ShapeCell | None = None) -> ArchConfig:
    """Depth-reduced config for the cost pass (hybrid: whole groups).

    The cost pass unrolls inner chunk loops; cap the chunk count at 32 by
    enlarging the SSM chunk for long sequences (chunk size is a tunable —
    larger chunks raise arithmetic intensity, fitting the MXU; noted in
    EXPERIMENTS.md §Methodology)."""
    over = {}
    if cell is not None and cell.kind != "decode":
        over["ssm_chunk"] = max(cfg.ssm_chunk, cell.seq_len // 32)
        over["q_chunk"] = max(cfg.q_chunk, cell.seq_len // 32)
    if cfg.family == "hybrid":
        return replace(cfg, n_layers=n * cfg.hybrid_period, **over)
    if cfg.enc_layers:
        return replace(cfg, n_layers=n, enc_layers=n, **over)
    return replace(cfg, n_layers=n, **over)


def layer_units(cfg: ArchConfig) -> float:
    """How many 'units' the full model has in reduce_layers units."""
    if cfg.family == "hybrid":
        return cfg.n_layers / cfg.hybrid_period
    return float(cfg.n_layers)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def lower_compile(cfg, cell, mesh, *, unroll, variant=None):
    fn, args, in_sh, out_sh = build_cell(cfg, cell, mesh, unroll=unroll,
                                         variant=variant)
    t0 = time.time()
    with mesh, mesh_context(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
    t2 = time.time()
    return lowered, compiled, {"lower_s": round(t1 - t0, 2),
                               "compile_s": round(t2 - t1, 2)}


def cost_snapshot(compiled, n_devices: int) -> dict:
    ca = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text(), n_devices)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_link_bytes": colls["link_bytes"]["total"],
            "coll_by_kind": colls["link_bytes"],
            "coll_counts": colls["counts"]}


def run_cell(arch: str, shape: str, multi_pod: bool, do_cost: bool = True,
             cost_layers=(2, 4), variant: str | None = None) -> dict:
    cfg = ARCHS[arch]
    if cfg.family == "hybrid":
        cost_layers = (1, 2)  # hybrid units are whole 6-layer groups
    cell = SHAPE_CELLS[shape]
    rec: dict = {"arch": arch, "shape": shape, "variant": variant or "baseline",
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    ok, why = cell_supported(cfg, cell)
    if not ok:
        rec["status"] = "SKIP"
        rec["reason"] = why
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    try:
        # pass 1: full config, scanned — the compile deliverable
        _, compiled, times = lower_compile(cfg, cell, mesh, unroll=False,
                                            variant=variant)
        ma = compiled.memory_analysis()
        rec.update(times)
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        rec["scanned_cost"] = cost_snapshot(compiled, n_dev)
        del compiled
        rec["status"] = "PASS"
    except Exception as e:
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-2000:]
        return rec

    if not do_cost:
        return rec
    try:
        # pass 2: two-point unrolled extrapolation (single-pod roofline only
        # runs it once per mesh; terms are per-device so mesh matters)
        l1, l2 = cost_layers
        snaps = {}
        for ln in (l1, l2):
            _, comp, t = lower_compile(reduce_layers(cfg, ln, cell), cell, mesh,
                                       unroll=True, variant=variant)
            snaps[ln] = cost_snapshot(comp, n_dev)
            snaps[ln]["compile_s"] = t["compile_s"]
            del comp
        units = layer_units(cfg)
        full = {}
        for k in ("flops", "bytes", "coll_link_bytes"):
            per = (snaps[l2][k] - snaps[l1][k]) / (l2 - l1)
            full[k] = snaps[l2][k] + (units - l2) * per
            full[f"{k}_per_layer"] = per
        rec["cost_points"] = snaps
        rec["cost"] = full
        # roofline terms (per-device seconds)
        hw = peaks(PRODUCTION_KIND)
        rec["roofline"] = {
            "compute_s": full["flops"] / hw.flops_bf16,
            "memory_s": full["bytes"] / hw.hbm_bw,
            "collective_s": full["coll_link_bytes"] / hw.ici_bw,
        }
        mf = aflops.model_flops(cfg, cell)
        rec["model_flops_global"] = mf
        rec["model_flops_per_dev"] = mf / n_dev
        rec["useful_flop_ratio"] = (mf / n_dev) / max(full["flops"], 1.0)
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["dominant"] = dom
        step_time = max(rec["roofline"].values())
        rec["roofline_fraction"] = (mf / n_dev / hw.flops_bf16) / max(step_time, 1e-12)
    except Exception as e:
        rec["cost_error"] = f"{type(e).__name__}: {e}"
        rec["trace_cost"] = traceback.format_exc()[-2000:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--no-cost", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="perf levers, '+'-joined: causal_skip, ws_decode, "
                         "remat_off, qchunkN")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPE_CELLS) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    if os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"], r.get("variant", "baseline"))
            for r in results}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = (arch, shape, "2x16x16" if mp else "16x16",
                       args.variant or "baseline")
                if args.skip_existing and key in done:
                    continue
                t0 = time.time()
                # cost pass only on the single-pod mesh (the roofline table's
                # scope); multi-pod proves the pod axis shards
                rec = run_cell(arch, shape, mp, do_cost=not args.no_cost and not mp,
                               variant=args.variant)
                rec["wall_s"] = round(time.time() - t0, 1)
                results = [r for r in results if
                           (r["arch"], r["shape"], r["mesh"],
                            r.get("variant", "baseline")) != key] + [rec]
                json.dump(results, open(args.out, "w"), indent=1)
                dom = rec.get("dominant", "-")
                print(f"[{arch} x {shape} x {key[2]}] {rec['status']} "
                      f"wall={rec['wall_s']}s dominant={dom} "
                      f"{rec.get('error', '')}", flush=True)

    n_pass = sum(r["status"] == "PASS" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n== dry-run summary: {n_pass} PASS / {n_skip} SKIP / {n_fail} FAIL ==")


if __name__ == "__main__":
    main()
