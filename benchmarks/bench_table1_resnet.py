"""Paper Table I: ResNet compression — FK vs PK kernel representation x
FP vs FS LCC algorithm, after group-lasso regularized training.

CPU-scale protocol (DESIGN.md): a reduced pre-act ResNet is trained on the
procedural-textures stand-in with group-lasso prox on the eq.-(11) kernel
groups; every conv layer is then decomposed all four ways.  The paper's
qualitative claims checked here: FS >= FP (esp. for small equivalent
matrices), both >= reg-training-only, PK taller than FK.  The full ResNet-34
config is also instantiated (random init) and a sampled subset of its conv
matrices decomposed to show scale behaviour.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compress import CompressionConfig, compress_conv_kernel
from repro.core.cost import ModelCostReport
from repro.core.group_lasso import group_prox_rows_np
from repro.data.synthetic import batches, textures_like
from repro.models.resnet import (conv_kernels, init_resnet, resnet34_config,
                                 resnet_forward, resnet_loss, resnet_small_config)
from repro.optim.optimizers import sgd


def train_small(epochs: int = 12, lam: float = 8e-3):
    cfg = resnet_small_config(classes=6)
    xs, ys = textures_like(512, size=24, classes=6, seed=0)
    xte, yte = textures_like(128, size=24, classes=6, seed=1)
    params = init_resnet(jax.random.PRNGKey(0), cfg)
    opt = sgd(momentum=0.9)
    state = opt.init(params)
    grad = jax.jit(jax.value_and_grad(resnet_loss))
    lr = 0.05

    def prox_convs(params, thresh):
        # eq. (11): groups = kernel rows of the per-input-channel matrices
        for blk in params["blocks"]:
            for name in ("conv1", "conv2"):
                k = np.asarray(blk[name], np.float64)  # [N, K, O, O]
                n, kk, o, _ = k.shape
                g = k.transpose(1, 0, 2, 3).reshape(kk * n, o * o)
                g = group_prox_rows_np(g, thresh)
                blk[name] = jnp.asarray(
                    g.reshape(kk, n, o, o).transpose(1, 0, 2, 3), jnp.float32)
        return params

    losses = []
    for ep in range(epochs):
        for xb, yb in batches(xs, ys, 64, seed=ep):
            loss, g = grad(params, jnp.asarray(xb), jnp.asarray(yb))
            params, state = opt.update(g, state, params, lr)
            params = prox_convs(params, lr * lam)
            losses.append(float(loss))
    logits = resnet_forward(params, jnp.asarray(xte))
    acc = float((jnp.argmax(logits, -1) == jnp.asarray(yte)).mean())
    return params, acc


def run(csv_rows: list[str]) -> None:
    t0 = time.time()
    params, acc = train_small()
    kernels = conv_kernels(params)[1:]  # skip the 3-channel stem
    for conv_method in ("fk", "pk"):
        for alg in ("fp", "fs"):
            rep = ModelCostReport()
            for name, k in kernels:
                compress_conv_kernel(name, np.asarray(k, np.float64),
                                     CompressionConfig(algorithm=alg,
                                                       conv_method=conv_method,
                                                       weight_sharing=False),
                                     rep)
            row = (f"table1_resnet,small,method={conv_method},alg={alg},"
                   f"acc={acc:.3f},ratio_regtrain={rep.ratio('pruned'):.2f},"
                   f"ratio_lcc={rep.ratio('lcc'):.2f}")
            print(row, flush=True)
            csv_rows.append(row)
    # scale demonstration: ResNet-34 (random init), sampled channels
    cfg34 = resnet34_config()
    p34 = init_resnet(jax.random.PRNGKey(1), cfg34)
    big = [kv for kv in conv_kernels(p34) if kv[1].shape[1] >= 64][:2]
    for conv_method in ("fk", "pk"):
        rep = ModelCostReport()
        for name, k in big:
            compress_conv_kernel(name, np.asarray(k, np.float64),
                                 CompressionConfig(algorithm="fs",
                                                   conv_method=conv_method,
                                                   weight_sharing=False),
                                 rep, channel_subsample=16)
        row = (f"table1_resnet,resnet34_sampled,method={conv_method},alg=fs,"
               f"ratio_lcc={rep.ratio('lcc'):.2f}")
        print(row, flush=True)
        csv_rows.append(row)
    csv_rows.append(f"table1_wall_s,{time.time() - t0:.1f},")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run([])
