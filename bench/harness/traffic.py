"""Traffic from a mix's parameters and the run's seed.

One general generator reads every mix file (``bench/traffic/<mix>.json``):

- ``loop``: ``"open"`` (independent users: requests are due on a schedule at
  the cell's ``rate_per_s``, whatever the server does) or ``"closed"`` (one
  client per slot; each sends its next request when the last finished).
- ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``, with an optional
  ``grid`` that prompt lengths are rounded up to.  The program compiles
  host-side prefill programs for every distinct prompt length, so a mix
  with a length per token puts a cell's first (compiling) run past its
  limit (PERF.md, cells).
- ``requests_per_client`` (closed loop).

Every seed gets the same set of lengths and of inter-arrival gaps — the
distribution's quantiles at (i + 1/2)/n — in its own order, so seeds differ
in what is sent when and in the tokens, not in how much work a run holds.
An open loop's order is drawn in blocks (:func:`blocked_order`): every ten
consecutive requests hold one length, and one gap, from each tenth of the
distribution, so each stretch of the window carries the same mix whatever
the seed.  A window shorter than the longest requests would otherwise
measure how the seed happened to order them.
Prompt tokens come from a sparse random Markov chain (a copy of
``repro.data.synthetic.MarkovLM``'s sampler, kept here so the program cannot
change the yardstick).  The program receives only the generated requests.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["Request", "Plan", "MarkovLM", "quantile_lengths", "blocked_order",
           "generate", "warmup_prompts", "rng_for", "seed_u32"]

# independent random streams drawn from one --seed
_STREAM = {"weights": 1, "lengths": 2, "arrivals": 3, "tokens": 4,
           "warmup": 5, "sample": 6, "artifact": 7, "tail": 8}


def rng_for(seed: int, stream: str, *sub: int) -> np.random.Generator:
    """A numpy generator for one purpose (and sub-part) of one run; any
    integer seed."""
    return np.random.default_rng([int(seed) % (1 << 64), _STREAM[stream],
                                  *sub])


def seed_u32(seed: int, stream: str) -> int:
    """A 32-bit seed for ``jax.random.PRNGKey`` drawn from the run's seed."""
    return int(rng_for(seed, stream).integers(0, 1 << 32, dtype=np.uint64))


@dataclass
class Request:
    rid: int
    client: int
    due_s: float  # seconds after the window opens (open loop); 0 closed
    prompt: list[int]
    max_new: int


@dataclass
class Plan:
    loop: str
    requests: list[Request]  # open: in due order; closed: client-major

    def client_queue(self, client: int) -> list[Request]:
        return [r for r in self.requests if r.client == client]


class MarkovLM:
    """Sparse random Markov chain over ``vocab`` tokens with branching ``k``
    (``repro.data.synthetic.MarkovLM``'s construction), sampled in batch."""

    def __init__(self, vocab: int, k: int, rng: np.random.Generator):
        self.vocab = vocab
        self.succ = rng.integers(0, vocab, size=(vocab, k))
        p = np.exp(rng.standard_normal((vocab, k)))
        self.cdf = np.cumsum(p / p.sum(1, keepdims=True), axis=1)

    def sample(self, batch: int, seq_len: int,
               rng: np.random.Generator) -> np.ndarray:
        toks = np.empty((batch, seq_len), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, batch)
        for t in range(1, seq_len):
            cur = toks[:, t - 1]
            choice = (rng.random(batch)[:, None] < self.cdf[cur]).argmax(1)
            toks[:, t] = self.succ[cur, choice]
        return toks


def blocked_order(n: int, rng: np.random.Generator, bands: int = 10
                  ) -> np.ndarray:
    """A permutation of range(n) whose every run of ``bands`` consecutive
    places holds one index from each of ``bands`` contiguous bands of
    range(n) (the last run may be short), drawn from ``rng``."""
    groups = [rng.permutation(b) for b in np.array_split(np.arange(n), bands)
              if b.size]
    out = []
    for j in range(max(g.size for g in groups)):
        block = [g[j] for g in groups if j < g.size]
        out.extend(block[i] for i in rng.permutation(len(block)))
    return np.array(out, np.int64)


def quantile_lengths(spec: dict, n: int, rng: np.random.Generator | None
                     ) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 1/2)/n, rounded up
    to ``grid``, clipped to [min, max]: in an order drawn from ``rng``, or
    ascending for ``rng=None``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.floor(v).astype(np.int64)
    grid = int(spec.get("grid", 1))
    v = -(-v // grid) * grid
    v = np.clip(v, lo, hi)
    return v if rng is None else v[rng.permutation(n)]


def _prompts(lens: np.ndarray, vocab: int, k: int,
             rng: np.random.Generator) -> list[list[int]]:
    lm = MarkovLM(vocab, k, rng)
    toks = lm.sample(len(lens), int(lens.max()), rng)
    return [toks[i, :n].tolist() for i, n in enumerate(lens)]


def generate(traffic: dict, deployment: dict, *, vocab: int, seed: int,
             seconds: float, tail: float = 0.0) -> Plan:
    """The run's requests.  ``tail``: an open loop goes on for that many
    seconds past the window with requests of its own, drawn from a stream
    the window's do not use, so the window's traffic is the same with or
    without it."""
    plan = _generate(traffic, deployment, vocab, seconds,
                     lambda stream: rng_for(seed, stream))
    if tail > 0 and traffic["loop"] == "open":
        extra = _generate(traffic, deployment, vocab, tail,
                          lambda stream: rng_for(seed, "tail",
                                                 _STREAM[stream]))
        n = len(plan.requests)
        for i, r in enumerate(extra.requests):
            r.rid, r.due_s = n + i, seconds + r.due_s
        plan.requests += extra.requests
    return plan


def _generate(traffic, deployment, vocab, seconds, rng) -> Plan:
    k = int(traffic.get("markov_branching", 8))
    if traffic["loop"] == "open":
        rate = float(deployment["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u) / rate
        gaps = gaps[blocked_order(n, rng("arrivals"))]
        due = np.cumsum(gaps) - gaps[0]
        clients = np.zeros(n, np.int64)
    elif traffic["loop"] == "closed":
        n_clients = int(deployment["n_slots"])
        per_client = int(traffic["requests_per_client"])
        n = n_clients * per_client
        due = np.zeros(n)
        clients = np.repeat(np.arange(n_clients), per_client)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    lrng = rng("lengths")
    if traffic["loop"] == "open":
        plen = quantile_lengths(traffic["prompt_len"], n, None)[
            blocked_order(n, lrng)]
        olen = quantile_lengths(traffic["output_len"], n, None)[
            blocked_order(n, lrng)]
    else:
        plen = quantile_lengths(traffic["prompt_len"], n, lrng)
        olen = quantile_lengths(traffic["output_len"], n, lrng)
    prompts = _prompts(plen, vocab, k, rng("tokens"))
    reqs = [Request(rid=i, client=int(clients[i]), due_s=float(due[i]),
                    prompt=prompts[i], max_new=int(olen[i]))
            for i in range(n)]
    return Plan(loop=traffic["loop"], requests=reqs)


def warmup_prompts(plan: Plan, *, vocab: int, seed: int) -> list[list[int]]:
    """One prompt of every distinct length the plan sends (longest first),
    drawn from a stream the measured traffic does not use."""
    lens = np.array(sorted({len(r.prompt) for r in plan.requests},
                           reverse=True))
    return _prompts(lens, vocab, 8, rng_for(seed, "warmup"))
