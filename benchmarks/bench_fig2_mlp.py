"""Paper Fig. 2: compression-accuracy tradeoff of the MLP first layer.

Reproduction on the procedural-digits stand-in (no MNIST offline — DESIGN.md):
for each lambda, train MLP-300 with ProxSGD (eq. (7)), then report the
compression ratio after (a) pruning only, (b) + weight sharing, (c) + LCC —
the dots / crosses / triangles of Fig. 2 — plus the two claims quantified in
Sec. IV-A: LCC-on-pruned gain (paper: 2.4-3.1x) and LCC-direct-on-unpruned
gain (paper: ~2x) whose quotient is the "combining gain" (paper: up to ~50%).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as core
from repro.core.csd import adds_csd_matrix
from repro.core.lcc import lcc_decompose
from repro.data.synthetic import batches, digits_like
from repro.models.mlp import init_mlp, mlp_accuracy, mlp_loss
from repro.optim.optimizers import prox_sgd, step_decay

LAMBDAS = (0.05, 0.1, 0.2)
EPOCHS = 10


def train_one(lam: float, hidden: int = 300, epochs: int = EPOCHS):
    xs, ys = digits_like(2048, seed=0)
    xte, yte = digits_like(512, seed=1)
    params = init_mlp(jax.random.PRNGKey(0), hidden=hidden, classes=10)
    opt = prox_sgd(momentum=0.9, prox_spec={"fc1/w": (lam, "columns")})
    state = opt.init(params)
    lr = step_decay(0.1, 0.95, 10)
    grad = jax.jit(jax.grad(mlp_loss))
    upd = jax.jit(lambda g, s, p, l: opt.update(g, s, p, l))
    for ep in range(epochs):
        for xb, yb in batches(xs, ys, 128, seed=ep):
            g = grad(params, jnp.asarray(xb), jnp.asarray(yb))
            params, state = upd(g, state, params, lr(ep))
    acc = float(mlp_accuracy(params, jnp.asarray(xte), jnp.asarray(yte)))
    return params, acc, (jnp.asarray(xte), jnp.asarray(yte))


def run(csv_rows: list[str]) -> None:
    t0 = time.time()
    # the paper's baseline: the UNregularized model's first layer in CSD
    params0, acc0, _ = train_one(0.0)
    w0 = np.asarray(params0["fc1"]["w"], np.float64)
    baseline = adds_csd_matrix(w0, 8)
    # paper Sec. IV-A reference point: LCC directly on the unpruned matrix ~ 2x
    d_direct = lcc_decompose(w0, algorithm="fp", frac_bits=8)
    direct_ratio = baseline / max(d_direct.num_adds(), 1)
    csv_rows.append(f"fig2_mlp,baseline,acc={acc0:.3f},adds={baseline},"
                    f"direct_lcc_ratio={direct_ratio:.2f}")
    print(csv_rows[-1], flush=True)

    for lam in LAMBDAS:
        params, acc, (xte, yte) = train_one(lam)
        w1 = np.asarray(params["fc1"]["w"], np.float64)
        rep = core.ModelCostReport()
        cd = core.compress_dense_matrix(
            "fc1", w1, core.CompressionConfig(algorithm="fs"), rep)
        lc = rep.layers[0]
        # compressed accuracy (the y-axis of Fig. 2)
        eff = np.zeros_like(w1)
        eff[:, cd.kept_columns] = cd.effective
        fc1 = lambda x, m=eff: x @ jnp.asarray(m, jnp.float32).T  # noqa: E731
        acc_lcc = float(mlp_accuracy(params, xte, yte, fc1_matvec=fc1))
        # all ratios vs the common unregularized baseline (paper protocol)
        r_pruned = baseline / max(lc.stage_adds["pruned"], 1)
        r_shared = baseline / max(lc.stage_adds["shared"], 1)
        r_lcc = baseline / max(lc.stage_adds["lcc"], 1)
        lcc_gain_on_pruned = lc.stage_adds["shared"] / max(lc.stage_adds["lcc"], 1)
        combining_gain = lcc_gain_on_pruned / max(direct_ratio, 1e-9)
        row = (f"fig2_mlp,lam={lam},acc={acc:.3f},kept={lc.extra['kept_cols']},"
               f"clusters={lc.extra['clusters']},ratio_pruned={r_pruned:.2f},"
               f"ratio_shared={r_shared:.2f},ratio_lcc={r_lcc:.2f},"
               f"acc_lcc={acc_lcc:.3f},lcc_gain_on_pruned={lcc_gain_on_pruned:.2f},"
               f"combining_gain={combining_gain:.2f}")
        print(row, flush=True)
        csv_rows.append(row)
    csv_rows.append(f"fig2_mlp_wall_s,{time.time() - t0:.1f},")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run([])
