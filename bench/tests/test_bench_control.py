"""The output check catches what it must: its control (the reference in
float8) and the faults a served cell can have, each planted in the timed
path, turn ``correct`` false at a tiny size on the CPU.

Faults that a serving cell cannot have are not planted: it keeps no
optimizer state or mean over a batch, and a one-chip cell exchanges
nothing between chips."""
import pytest

import bench_testlib

SEED = 3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testlib.tiny_root(tmp_path_factory.mktemp("control"))


def test_sound_run_passes_and_control_fails(root, monkeypatch):
    sound = bench_testlib.run_tiny(monkeypatch, root, "tiny-chat", seed=SEED)
    assert sound["correct"]
    r = bench_testlib.run_tiny(monkeypatch, root, "tiny-chat", seed=SEED,
                               control=True)
    c = r["checks"]
    # the control, judged in the program's place, comes out not correct
    assert not r["correct"]
    assert c["logit_gap"]["value"] > c["logit_gap"]["limit"]
    assert c["tokens_checked"]["value"] >= c["tokens_checked"]["limit"]
    # on the same sample the program's own reading passes, as it does alone
    assert c["program_gap"]["value"] <= c["logit_gap"]["limit"]


def _token_altered(monkeypatch):
    from repro.models import api

    real = api.sample_tokens

    def altered(logits, keys, temperature):
        return (real(logits, keys, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(api, "sample_tokens", altered)


def _state_unchanged(monkeypatch):
    from repro.models import api

    real = api.decode

    def frozen(params, cfg, state, token, pos, **kw):
        logits, _ = real(params, cfg, state, token, pos, **kw)
        return logits, state

    monkeypatch.setattr(api, "decode", frozen)


@pytest.mark.parametrize("plant", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_planted_fault_is_not_correct(root, monkeypatch, plant):
    plant(monkeypatch)
    r = bench_testlib.run_tiny(monkeypatch, root, "tiny-chat", seed=SEED)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]
