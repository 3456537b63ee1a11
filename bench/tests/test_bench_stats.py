"""Metric arithmetic: latency from the due time over all requests due in
the window, rates over the whole window, utilisation against the peaks
table, and an unknown device kind refused."""
from types import SimpleNamespace

import pytest

import bench_testlib
from harness import flops, stats
from harness.drive import Record, Window
from harness.peaks import peaks
from harness.spec import load_module


def _win():
    recs = {
        0: Record(rid=0, due=0.0, prompt_len=10, max_new=4, admit=0.5,
                  first=1.0, last=4.0, n_tok=4, done=True),
        1: Record(rid=1, due=2.0, prompt_len=20, max_new=4, admit=2.5,
                  first=3.0, last=3.0, n_tok=1),
        2: Record(rid=2, due=8.0, prompt_len=30, max_new=4),  # never served
        3: Record(rid=3, due=12.0, prompt_len=30, max_new=4),  # not due
    }
    return Window(seconds=10.0, records=recs,
                  steps=[(0.9, 1.0, 1), (2.9, 3.0, 2), (3.9, 4.0, 1)],
                  admits=[(0.4, 0.5, 1), (2.4, 2.6, 1)])


def test_latency_counts_from_the_due_time_and_the_unserved():
    w = _win()
    assert sorted(stats.ttft_s(w)) == pytest.approx([1.0, 1.0, 2.0])
    assert stats.queue_wait_s(w) == pytest.approx([0.5, 0.5, 2.0])
    assert stats.tpot_s(w) == pytest.approx([1.0])


def test_percentile_over_all_requests():
    v = list(range(1, 101))
    assert stats.pctl(v, 95) == pytest.approx(95.05)
    assert stats.pctl([], 95) is None


def test_rates_and_means_over_the_whole_window():
    w = _win()
    assert stats.decode_tokens(w) == 4
    reader = load_module(bench_testlib.BENCH.parent, "metrics",
                         "decode_tok_s")
    assert reader.read(SimpleNamespace(window=w)) == pytest.approx(0.4)
    assert stats.step_mean_s(w) == pytest.approx(0.1)
    assert stats.admit_pass_s(w) == pytest.approx([0.1, 0.2])


def test_mfu_against_the_peaks_table():
    w = _win()
    shape = flops.ModelShape(layers=2, d_model=64, heads=4, kv_heads=2,
                             head_dim=16, d_ff=128, vocab=256)
    per = flops.matmul_params(shape)
    assert per == 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128) \
        + 256 * 64
    f = flops.request_decode_flops(shape, 10, 4)
    assert f == pytest.approx(sum(flops.decode_flops_per_token(shape, 10 + j)
                                  for j in range(4)))
    pk = peaks("TPU v5 lite")
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "intermediate_size": 128,
           "vocab_size": 256, "num_hidden_layers": 2,
           "tie_word_embeddings": True}
    ctx = SimpleNamespace(window=w, peaks=pk, chips=1,
                          cell=SimpleNamespace(config=cfg))
    reader = load_module(bench_testlib.BENCH.parent, "metrics",
                         "decode_step.mfu")
    want = sum(flops.request_decode_flops(shape, r.prompt_len, r.n_tok)
               for r in w.records.values()) / (10.0 * 197e12) * 100
    assert reader.read(ctx) == pytest.approx(want)
    assert reader.read(SimpleNamespace(**{**vars(ctx), "peaks": None})) \
        is None


def test_lcc_cost_counts_terms_not_layout():
    adds, nbytes = flops.lcc_site_cost(1000, 11, 2048, 32)
    assert adds == 32000
    assert nbytes == 1000 * 6 + 4 * 32 * (11 + 2048)


def test_unknown_device_kind_is_refused():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
