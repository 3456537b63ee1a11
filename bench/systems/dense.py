"""A dense decoder served from seeded weights: the program's ``ServingEngine``
over the weights ``bench/references/<reference>.py`` makes in the serving
dtype, on one chip."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from harness import program
from harness.spec import load_module
from harness.traffic import seed_u32

__all__ = ["System", "build"]


class System:
    """What a cell serves, and what its check needs afterwards."""

    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, seed
        self.ref = load_module(cell.root, "references",
                               cell.config["reference"])
        self.spec = self.ref.spec_from_config(cell.config)
        self.cfg = program.arch_config(cell.config, cell.config_name)
        if cell.chips != 1:
            raise ValueError(f"{cell.name}: a dense system serves one chip")
        self.split: dict[str, float] = {}
        self.lcc_sites: list | None = None
        self.engine = None

    def weights(self, dtype):
        """The seeded weights, program layout."""
        return self.ref.make_params(self.spec, seed_u32(self.seed, "weights"),
                                    dtype)

    def build(self):
        t0 = time.perf_counter()
        params = self.weights(jnp.dtype(self.cell.config["serve_dtype"]))
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        self.engine = program.build_engine(
            params=params, cfg=self.cfg, deployment=self.cell.deployment,
            seed=self.seed)
        self.split.update(weights_s=t1 - t0,
                          engine_s=time.perf_counter() - t1)
        return self

    def reference_params(self):
        """The served weights made again from the seed; the reference
        reads them as float32."""
        return self.weights(jnp.dtype(self.cell.config["serve_dtype"]))


def build(cell, seed: int, devices) -> System:
    return System(cell, seed, devices).build()
