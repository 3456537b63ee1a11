"""Decide ``correct``: served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests it
served — the one with the most served tokens first, then others in a seeded
order until ``check_tokens`` served tokens are in it — is run once through
the reference (prompt plus served tokens, float32, highest precision).  At
every served position the *gap* is the reference's best logit minus the
reference's logit of the token the program served; the run's number is the
widest gap.  Greedy decoding serves the reference's best token up to
rounding, so a sound program's widest gap stays small, and a wrong cache
position, mask, kernel or token moves it by the logits' own scale.

Finished requests are sampled first; where they hold fewer than
``check_tokens`` served tokens (long outputs in a short window), requests in
flight at the close add the tokens they had streamed by then.

The control (``quant="fp8"``) reads, at the same positions, the gap of the
token the reference computed with float8 weights would put first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import rng_for

__all__ = ["sample", "widest_gaps"]

BUCKET = 256  # reference sequences are padded to a multiple of this


def sample(win, results: dict, *, seed: int, check_tokens: int
           ) -> list[tuple[list[int], list[int]]]:
    """(prompt, served tokens) pairs: finished requests first, the one with
    the most tokens leading; in-flight requests after, as needed."""
    def served(sid):
        res = results.get(sid)
        return [] if res is None else list(res.tokens[res.prompt_len:])

    pool = [(sid, r) for sid, r in sorted(win.records.items())
            if r.error is None and r.n_tok > 0 and sid in results]
    done = [p for p in pool if p[1].done]
    live = [p for p in pool if not p[1].done]
    rng = rng_for(seed, "sample")
    out, n = [], 0
    for group in (done, live):
        if not group or n >= check_tokens:
            continue
        order = sorted(group, key=lambda p: -len(served(p[0])))
        head, rest = order[0], order[1:]
        rest = [rest[i] for i in rng.permutation(len(rest))]
        for sid, r in [head] + rest:
            if n >= check_tokens:
                break
            toks = served(sid)
            res = results[sid]
            out.append((list(res.tokens[:res.prompt_len]), toks))
            n += len(toks)
    return out


@jax.jit
def _gaps(logits, targets, ctl_first):
    best = jnp.max(logits, axis=-1)
    served = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    ctl = jnp.take_along_axis(logits, ctl_first[:, None], axis=-1)[:, 0]
    return best - served, best - ctl


def widest_gaps(ref, params, spec, pairs, *, control: bool = False
                ) -> dict:
    """Widest served-token gap over ``pairs`` (and the control's, with
    ``control``), with the number of served tokens compared."""
    worst, worst_ctl, n = 0.0, 0.0, 0
    for prompt, toks in pairs:
        if not toks:
            continue
        seq = prompt + toks[:-1]
        t = len(seq)
        tp = -(-t // BUCKET) * BUCKET
        ids = np.zeros(tp, np.int32)
        ids[:t] = seq
        lo = len(prompt) - 1  # position whose logits predict toks[0]
        logits = ref.forward_logits(params, spec, jnp.asarray(ids))
        tgt = np.zeros(tp, np.int32)
        tgt[lo:lo + len(toks)] = toks
        ctl_first = jnp.zeros(tp, jnp.int32)
        if control:
            ctl_first = jnp.argmax(ref.forward_logits(
                params, spec, jnp.asarray(ids), quant="fp8"), axis=-1)
        g, gc = _gaps(logits, jnp.asarray(tgt), ctl_first.astype(jnp.int32))
        g = np.asarray(g)[lo:lo + len(toks)]
        gc = np.asarray(gc)[lo:lo + len(toks)]
        worst = max(worst, float(g.max()))
        worst_ctl = max(worst_ctl, float(gc.max()))
        n += len(toks)
    out = {"logit_gap": worst, "tokens": n}
    if control:
        out["control_gap"] = worst_ctl
    return out
