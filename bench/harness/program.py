"""The harness's one door into the program under test.

The benchmark takes from the program only the system under test (its
``ServingEngine`` and ``Scheduler``, its configuration class, its compile
cache setting and, for a compressed artifact, its artifact classes) and
gives it only generated weights and requests.  Everything else — traffic,
metric arithmetic, trace reduction, the reference — lives in ``bench/``.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field

from .spec import ROOT

__all__ = ["import_program", "arch_config", "CompileCounter",
           "enable_cache", "build_engine"]


def import_program(root=ROOT):
    """Put the checkout's ``src`` first on the path and import the program.
    A checkout without it cannot run the benchmark: this raises."""
    src = str(root / "src")
    if not (root / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no program sources at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401

    return repro


def arch_config(config: dict, name: str):
    """The program's ``ArchConfig`` for a configuration file (Hugging Face
    keys, plus ``norm`` and ``serve_dtype``)."""
    import_program()
    from repro.configs.base import ArchConfig

    h = int(config["num_attention_heads"])
    return ArchConfig(
        name=name, family="dense",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]), n_heads=h,
        n_kv_heads=int(config.get("num_key_value_heads", h)),
        d_ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        head_dim=config.get("head_dim"),
        norm={"nonparametric": "nonparam", "rms": "rms"}[config["norm"]],
        rope_theta=float(config.get("rope_theta", 1e4)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        qkv_bias=bool(config.get("attention_bias", False)),
        param_dtype=config["serve_dtype"], compute_dtype=config["serve_dtype"])


@dataclass
class CompileCounter:
    """Counts JAX's traces and backend compiles (persistent-cache hits are
    traced but not compiled) and sums the compile seconds, between
    ``install`` and ``uninstall``."""
    traces: int = 0
    compiles: int = 0
    compile_s: float = 0.0
    cache_hits: int = 0
    _listeners: tuple = field(default=(), repr=False)

    def install(self) -> "CompileCounter":
        from jax._src import monitoring

        def dur(name, secs, **_):
            if name == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
            elif name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(dur)
        monitoring.register_event_listener(event)
        self._listeners = (dur, event)
        return self

    def uninstall(self) -> None:
        from jax._src import monitoring

        dur, event = self._listeners
        monitoring.unregister_event_duration_listener(dur)
        monitoring.unregister_event_listener(event)

    def snapshot(self) -> tuple[int, int, float, int]:
        return self.traces, self.compiles, self.compile_s, self.cache_hits


def enable_cache() -> str:
    """The program's persistent compile cache (inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), holding every program however
    quick to compile, so a cell's second run compiles nothing."""
    import_program()
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_engine(*, params=None, cfg=None, artifact=None, deployment: dict,
                 seed: int):
    """The program's serving engine as the cell deploys it: greedy decoding,
    paged KV in 16-token blocks (``kv_blocks`` of them where the cell sets
    a pool smaller than every slot's full context), the prefix cache at its
    default."""
    import_program()
    from repro.serving.engine import ServingEngine

    kw = dict(n_slots=int(deployment["n_slots"]),
              max_len=int(deployment["max_len"]), seed=seed % (1 << 31),
              temperature=0.0, kv_blocks=deployment.get("kv_blocks"))
    if artifact is not None:
        return ServingEngine(artifact=artifact, use_kernel=True, **kw)
    return ServingEngine(params, cfg, **kw)
