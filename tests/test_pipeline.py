"""Parallel compression pipeline: determinism vs the serial path, the
adds-budget allocator, content-addressed cache hits on tied weights,
structured progress events, and resume-after-SIGKILL through the CLI."""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.compress import (CompressibleConv, CompressibleDense,
                                 CompressionConfig, compress_conv_kernel,
                                 compress_dense_matrix, compress_model_params)
from repro.core.cost import ModelCostReport
from repro.pipeline import CompressionEvent, run_pipeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _units(n_dense=4, with_conv=False, seed=0, shape=(40, 20)):
    rng = np.random.default_rng(seed)
    units = [CompressibleDense(name=f"d{i}", weight=rng.standard_normal(shape))
             for i in range(n_dense)]
    if with_conv:
        units.append(CompressibleConv(
            name="c0", kernel=rng.standard_normal((8, 4, 3, 3))))
    return units


def _cfg():
    return CompressionConfig(algorithm="fp", weight_sharing=True,
                             max_share_rel_err=0.06)


def _assert_dense_bitwise(a, b):
    assert a.effective.tobytes() == b.effective.tobytes()
    assert np.array_equal(a.kept_columns, b.kept_columns)
    if a.shared is None:
        assert b.shared is None
    else:
        assert a.shared.labels.tobytes() == b.shared.labels.tobytes()
        assert a.shared.centroids.tobytes() == b.shared.centroids.tobytes()
    da, db = a.decomposition, b.decomposition
    assert da.col_slices == db.col_slices
    assert da.meta == db.meta
    assert da.to_dense().tobytes() == db.to_dense().tobytes()


def _assert_records_bitwise(ra, rb):
    assert set(ra) == set(rb)
    for n in ra:
        if isinstance(ra[n], dict):  # conv record
            assert ra[n]["lcc_adds"] == rb[n]["lcc_adds"]
            assert ra[n]["channels_nonzero"] == rb[n]["channels_nonzero"]
            for ch in ra[n]["decompositions"]:
                assert (ra[n]["decompositions"][ch].to_dense().tobytes()
                        == rb[n]["decompositions"][ch].to_dense().tobytes())
        else:
            _assert_dense_bitwise(ra[n], rb[n])


def _report_rows(report):
    return [(l.name, l.baseline_adds, l.stage_adds, l.stage_bytes)
            for l in report.layers]


# ------------------------------------------------------------- determinism


def test_parallel_bitwise_identical_to_serial():
    """Worker fan-out must not change a single bit of the output, and the
    serial wrapper must match the direct Algorithm-1 calls."""
    units = _units(n_dense=4, with_conv=True)
    cfg = _cfg()
    ref_rep = ModelCostReport()
    ref = {}
    for u in units:
        if isinstance(u, CompressibleDense):
            ref[u.name] = compress_dense_matrix(u.name, u.weight, cfg, ref_rep)
        else:
            ref[u.name] = compress_conv_kernel(u.name, u.kernel, cfg, ref_rep)

    serial = run_pipeline(units, cfg, n_workers=1)
    parallel = run_pipeline(units, cfg, n_workers=2)
    _assert_records_bitwise(ref, serial.records)
    _assert_records_bitwise(ref, parallel.records)
    assert _report_rows(ref_rep) == _report_rows(serial.report) \
        == _report_rows(parallel.report)

    out, rep = compress_model_params(units, cfg)  # the thin serial wrapper
    _assert_records_bitwise(ref, out)
    assert _report_rows(ref_rep) == _report_rows(rep)


# ----------------------------------------------------------------- events


def test_structured_progress_events():
    events = []
    units = _units(n_dense=3)
    run_pipeline(units, _cfg(), n_workers=1, progress=events.append)
    assert all(isinstance(e, CompressionEvent) for e in events)
    kinds = {e.kind for e in events}
    assert {"plan", "unit_start", "slice_done", "unit_done"} <= kinds
    done = [e for e in events if e.kind == "unit_done"]
    assert [e.unit for e in done] == [u.name for u in units]
    for e in done:
        assert e.adds_before > 0 and e.adds_after > 0
        assert e.wall_s >= 0
        assert e.unit in str(e)  # old string-callback consumers stay readable


# ------------------------------------------------------------ cache hits


def test_cache_hits_on_tied_weights():
    """Two units sharing one weight matrix: the second is free (same
    content-addressed jobs), and its record is bitwise identical."""
    rng = np.random.default_rng(3)
    w = rng.standard_normal((40, 20))
    units = [CompressibleDense(name="tied_a", weight=w),
             CompressibleDense(name="tied_b", weight=w.copy())]
    res = run_pipeline(units, _cfg(), n_workers=1)
    n_slices = len(res.records["tied_a"].decomposition.col_slices)
    assert res.stats["cache_hits"] >= n_slices  # all of tied_b's jobs
    assert res.stats["cache_misses"] == n_slices
    _assert_dense_bitwise(res.records["tied_a"], res.records["tied_b"])


def test_cache_persists_across_runs(tmp_path):
    units = _units(n_dense=3)
    cache = str(tmp_path / "cache")
    cold = run_pipeline(units, _cfg(), n_workers=1, cache_dir=cache)
    warm = run_pipeline(units, _cfg(), n_workers=2, cache_dir=cache)
    assert cold.stats["cache_hits"] == 0
    assert warm.stats["cache_misses"] == 0
    assert warm.stats["cache_hits"] == warm.stats["jobs"]
    _assert_records_bitwise(cold.records, warm.records)
    assert _report_rows(cold.report) == _report_rows(warm.report)


# ---------------------------------------------------------- adds budget


def test_budget_allocation_lands_within_5pct(tmp_path):
    units = _units(n_dense=6, with_conv=True, seed=1)
    cfg = _cfg()
    cache = str(tmp_path / "cache")
    rich = run_pipeline(units, cfg, n_workers=1, cache_dir=cache)
    floor = run_pipeline(
        units, CompressionConfig(algorithm="fs", snr_offset_db=-9.0,
                                 prune_tol=1e-4, max_share_rel_err=None),
        n_workers=1, cache_dir=cache)
    lo = floor.report.total_stage("lcc")
    hi = rich.report.total_stage("lcc")
    assert lo < hi
    for frac in (0.4, 0.8):
        budget = int(lo + frac * (hi - lo))
        res = run_pipeline(units, cfg, budget_adds=budget, n_workers=2,
                           cache_dir=cache)
        landed = res.report.total_stage("lcc")
        # verified via the ModelCostReport: inside the budget, within 5%
        assert landed <= budget
        assert landed >= 0.95 * budget
        assert res.budget_info["landed_adds"] == landed
        # the allocator chose real per-unit plans
        assert set(res.unit_configs) == {u.name for u in units}


def test_budget_below_floor_emits_floor_plan():
    units = _units(n_dense=2, seed=2)
    events = []
    res = run_pipeline(units, _cfg(), budget_adds=1, n_workers=1,
                       progress=events.append)
    assert res.report.total_stage("lcc") > 1  # floor, not a crash
    assert any(e.kind == "budget" and "below the adds floor" in e.detail
               for e in events)


def test_artifact_records_per_unit_plans(tmp_path):
    """Budget runs record the allocator's plans in the CompressedModel and
    round-trip them through save/load."""
    import jax

    from repro.configs import get_arch
    from repro.configs.base import reduced_config
    from repro.core.artifact import CompressedModel
    from repro.models import api

    cfg = reduced_config(get_arch("olmo-1b"), d_model=32, n_heads=2,
                         n_kv_heads=2, head_dim=16, d_ff=48, vocab=64,
                         n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    cache = str(tmp_path / "cache")
    base = api.compress_model(params, cfg, include="ffn.", cache_dir=cache)
    budget = int(0.7 * base.report.total_stage("lcc"))
    art = api.compress_model(params, cfg, include="ffn.", budget_adds=budget,
                             n_workers=2, cache_dir=cache)
    assert art.report.total_stage("lcc") <= budget
    assert art.unit_configs  # allocator plans recorded
    art.save(str(tmp_path / "art"))
    back = CompressedModel.load(str(tmp_path / "art"))
    assert back.unit_configs == art.unit_configs
    assert back.unit_config_for("ffn.gate.l0") == art.unit_configs["ffn.gate.l0"]
    assert back.pipeline_stats["jobs"] == art.pipeline_stats["jobs"]


# -------------------------------------------------------- resume semantics


def test_resume_refuses_mismatched_weights(tmp_path):
    units = _units(n_dense=2, seed=4)
    run_dir = str(tmp_path / "run")
    run_pipeline(units, _cfg(), n_workers=1, run_dir=run_dir)
    other = _units(n_dense=2, seed=5)
    with pytest.raises(ValueError, match="hash"):
        run_pipeline(other, _cfg(), n_workers=1, run_dir=run_dir, resume=True)


def test_resume_reuses_manifest_plans(tmp_path):
    units = _units(n_dense=3, seed=6)
    run_dir = str(tmp_path / "run")
    first = run_pipeline(units, _cfg(), n_workers=1, run_dir=run_dir)
    events = []
    second = run_pipeline(units, _cfg(), n_workers=1, run_dir=run_dir,
                          resume=True, progress=events.append)
    assert any(e.kind == "resume" for e in events)
    assert second.stats["cache_misses"] == 0  # every slice from the cache
    _assert_records_bitwise(first.records, second.records)


# ----------------------------------------------------- SIGKILL + resume


def _cli_cmd(out_dir, *extra):
    return [sys.executable, "-m", "repro.launch.compress", "--arch", "olmo-1b",
            "--quickstart", "--workers", "2", "--seed", "0", "--quiet",
            "--out", str(out_dir), *extra]


def _cli_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # a CPU compression run
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def test_resume_after_sigkill_matches_uninterrupted(tmp_path):
    """SIGKILL a pipeline run mid-way, resume it, and require the artifact to
    be bitwise-identical to an uninterrupted run."""
    from repro.core.artifact import CompressedModel

    killed_dir = tmp_path / "killed"
    clean_dir = tmp_path / "clean"

    # start, wait until a few slice results are durably cached, SIGKILL
    proc = subprocess.Popen(_cli_cmd(killed_dir), env=_cli_env(), cwd=REPO,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    cache = killed_dir / "cache"
    deadline = time.time() + 120
    killed = False
    while time.time() < deadline and proc.poll() is None:
        done = len(list(cache.glob("*.msgpack"))) if cache.exists() else 0
        if done >= 4:
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            killed = True
            break
        time.sleep(0.02)
    assert killed, "run finished before it could be killed; enlarge the model"
    assert not (killed_dir / "artifact").exists()  # it really died mid-run

    # resume to completion; a fresh run is the reference
    r = subprocess.run(_cli_cmd(killed_dir, "--resume"), env=_cli_env(),
                       cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    r2 = subprocess.run(_cli_cmd(clean_dir), env=_cli_env(), cwd=REPO,
                        capture_output=True, text=True, timeout=600)
    assert r2.returncode == 0, r2.stderr

    resumed = CompressedModel.load(str(killed_dir / "artifact"))
    clean = CompressedModel.load(str(clean_dir / "artifact"))
    _assert_records_bitwise(resumed.records, clean.records)
    assert _report_rows(resumed.report) == _report_rows(clean.report)
    # dense-effective params match bitwise too
    import jax
    la = jax.tree_util.tree_leaves(resumed.params)
    lb = jax.tree_util.tree_leaves(clean.params)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the resumed run actually reused the killed run's work
    stats = json.loads((killed_dir / "stats.json").read_text())
    assert stats["cache_hits"] >= 4


# ------------------------------------------------- prune-aware slice plans


def test_sparse_plan_bitwise_identical_and_stats():
    """Keep-in-place pruning (prune_tol < 0): all-dead slices are skipped
    (0-add zero pieces), partially-dead slices shrink to their live columns —
    and the result is bitwise identical across serial, parallel, and the
    direct Algorithm-1 call, with the dead groups accounted in the stats."""
    rng = np.random.default_rng(7)
    w = rng.standard_normal((24, 40))
    dead = rng.choice(40, 17, replace=False)
    w[:, dead] = 0.0  # prox-style exactly-dead input groups
    units = [CompressibleDense(name="sparse", weight=w),
             CompressibleDense(name="full",
                               weight=rng.standard_normal((24, 40)))]
    cfg = CompressionConfig(algorithm="fp", weight_sharing=False,
                            prune_tol=-1e-9)

    ref_rep = ModelCostReport()
    ref = {u.name: compress_dense_matrix(u.name, u.weight, cfg, ref_rep)
           for u in units}
    serial = run_pipeline(units, cfg, n_workers=1)
    parallel = run_pipeline(units, cfg, n_workers=2)
    _assert_records_bitwise(ref, serial.records)
    _assert_records_bitwise(ref, parallel.records)
    assert _report_rows(ref_rep) == _report_rows(serial.report) \
        == _report_rows(parallel.report)

    rec = serial.records["sparse"]
    assert np.array_equal(rec.kept_columns, np.arange(40))  # keep-in-place
    assert (rec.effective[:, dead] == 0.0).all()  # dead columns stay exact 0
    for res in (serial, parallel):
        assert res.stats["dead_groups"] >= 17
        assert res.stats["skipped_jobs"] + res.stats["shrunk_jobs"] >= 1
    assert serial.stats["skipped_jobs"] == parallel.stats["skipped_jobs"]
    assert serial.stats["shrunk_jobs"] == parallel.stats["shrunk_jobs"]


def test_drop_mode_stats_unchanged():
    """Drop-mode pruning (prune_tol >= 0) keeps its original slice jobs:
    nothing skipped or shrunk, cache keys bitwise-stable."""
    res = run_pipeline(_units(n_dense=2), _cfg(), n_workers=1)
    assert res.stats["skipped_jobs"] == 0
    assert res.stats["shrunk_jobs"] == 0


def test_workers_run_on_cpu_backend():
    """Slice workers are pinned to the CPU backend: the parent may hold the
    accelerator, which only one process can use at a time.  A fresh process
    whose environment names no platform starts the pool, so the pin must
    come from the worker start-up itself."""
    code = ("import os, jax\n"
            "from repro.pipeline import runner\n"
            "ex = runner._make_executor(1)\n"
            "print(ex.submit(os.getenv, 'JAX_PLATFORMS').result(timeout=120),"
            " ex.submit(jax.default_backend).result(timeout=120))\n"
            "ex.shutdown(wait=True)\n")
    env = {k: v for k, v in _cli_env().items() if k != "JAX_PLATFORMS"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["cpu", "cpu"]
