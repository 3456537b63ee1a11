"""Batched serving engine: prefill + decode with slot-based continuous batching.

The engine keeps a fixed decode batch of ``n_slots``; finished sequences free
their slot and queued requests are prefilled into it (one bulk ``api.prefill``
writes the slot's KV cache in a single forward).  Decoding is **device-side**:
one jitted dispatch per step fuses the forward pass, greedy/temperature
sampling (per-slot PRNG keys, so draws are independent of slot order and of
which other requests are in flight), position/budget bookkeeping and the
EOS/headroom ``done`` flags — the host receives a single small packed array
(sampled token + emit/done masks) per step instead of round-tripping logits.

Multi-device serving: pass ``mesh=`` and the engine places params with
:func:`repro.distributed.sharding.params_pspecs` (tensor-parallel on the
"model" axis where divisible, FSDP on "data" otherwise) and the KV/decode
state with :func:`~repro.distributed.sharding.decode_state_pspecs` (slots over
the batch axes), then jits the fused step with explicit in/out shardings so
every step runs partitioned without resharding-triggered recompiles.

Scheduling (queues, priorities, admission, streaming callbacks, failed-request
isolation) lives in :class:`repro.serving.scheduler.Scheduler`; ``generate()``
is a thin convenience wrapper over it.

Compressed serving is first-class, artifact-driven and family-agnostic:
compress offline with ``models.api.compress_model``, save the
:class:`~repro.core.artifact.CompressedModel`, and construct
``ServingEngine(artifact=art)``.  The engine builds a site-keyed
:class:`~repro.serving.executor.CompressedExecutor` over the artifact and the
model decode paths consult it *inside* the jitted step — attention q/k/v/o
(and MLA projections), FFN gate/up/down, per-expert MoE matrices (all experts
of a layer in ONE grouped launch), RWKV-6 time/channel mixes, Mamba2 in/out
projections and the whisper decoder all execute their LCC chains as fused
Pallas launches (``lcc_chain_matmul`` / ``lcc_group_matmul``, the shift-add
runtime the paper targets).  FS decompositions evaluate through their dense
equivalent; sites the artifact does not cover stay dense.
:func:`compress_ffn_for_serving` remains as the legacy FFN-only wrapper over
the same pipeline.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels import dispatch
from repro.models import api
from repro.obs import (MetricsRegistry, RequestTracer, StepProfiler,
                       install_compile_counter, span)
from repro.serving.executor import (CompressedExecutor, LCCMatvec,
                                    matvecs_from_artifact)
from repro.serving.kvpool import KVPool, empty_stats

__all__ = ["ServingEngine", "GenerationResult", "StepEvent", "LCCMatvec",
           "CompressedExecutor", "compress_ffn_for_serving"]


@dataclass
class GenerationResult:
    tokens: list[int]
    prompt_len: int
    finished: bool
    error: str | None = None
    # per-request telemetry the engine learned while serving this request
    # (prefill_s, cached_tokens, blocks_grown, cancelled, exhausted, ...);
    # the scheduler folds it into the request's span at retire
    stats: dict = field(default_factory=dict)


@dataclass(frozen=True)
class StepEvent:
    """One slot's outcome of a decode step: ``token is None`` means the slot
    finished without emitting (no decode headroom)."""
    rid: int
    token: int | None
    finished: bool


class ServingEngine:
    """``ServingEngine(params, cfg)`` serves raw weights; ``ServingEngine(
    artifact=compressed_model)`` serves a compression artifact (params and
    config come from the artifact, and every compressed site — any family —
    runs on the fused LCC kernel path unless ``use_kernel=False``).  Pass
    ``mesh=`` for sharded multi-device decode."""

    def __init__(self, params=None, cfg: ArchConfig | None = None, *,
                 artifact=None, n_slots: int = 8,
                 max_len: int = 512, eos_id: int | None = None,
                 temperature: float = 0.0, seed: int = 0,
                 use_kernel: bool = True, bulk_prefill: bool = True,
                 interpret: bool | None = None, mesh=None,
                 kv_block: int | None = 16, kv_blocks: int | None = None,
                 prefix_cache: bool = True, metrics=None, tracer=None):
        if artifact is not None:
            if cfg is None:
                cfg = artifact.config
            if params is None:
                params = artifact.params
        if params is None or cfg is None:
            raise ValueError("ServingEngine needs (params, cfg) or artifact=...")
        self.params = params
        self.cfg = cfg
        self.artifact = artifact
        self.n_slots = n_slots
        self.max_len = max_len
        # default per-request decode budget (submit()/Scheduler may override
        # per request); bounded by max_len anyway
        self.max_new = max_len
        self.eos = eos_id
        self.temp = temperature
        self.bulk_prefill = bulk_prefill
        self.mesh = mesh
        self._base_key = jax.random.PRNGKey(seed)
        # paged KV: attention families with bulk prefill keep their cache in a
        # block pool (kv_block=None restores the contiguous per-slot slabs);
        # ssm/hybrid recurrent state and whisper stay contiguous
        self.paged = (kv_block is not None and bulk_prefill
                      and api.paged_supported(cfg))
        self.pool: KVPool | None = None
        if self.paged:
            bs, mb, nb = api.paged_layout(cfg, max_len, kv_block, kv_blocks,
                                          n_slots)
            windowed = cfg.attn_window is not None
            self.pool = KVPool(
                n_slots=n_slots, n_blocks=nb - 1, block_size=bs, view_blocks=mb,
                # tail-extend prefill has no mrope path; windowed rings rewrite
                # shared prefixes as they wrap — both disable sharing
                prefix_cache=(prefix_cache and cfg.pos in ("rope", "none")),
                windowed=windowed)
            self.state = self._init_state(
                lambda: api.init_decode_state(cfg, n_slots, max_len,
                                              kv_block=kv_block,
                                              kv_blocks=kv_blocks))
            self._pool_leaves = ("c_kv", "k_rope") if "c_kv" in self.state else ("k", "v")
            self._tbl_host = np.zeros((n_slots, mb), np.int32)
            self._extend_fns: dict[int, object] = {}
        else:
            self.state = self._init_state(
                lambda: api.init_decode_state(cfg, n_slots, max_len))
        # host mirrors of the device-side per-slot control state
        self.pos = np.zeros(n_slots, np.int64)
        self.active = np.zeros(n_slots, bool)
        self._last_tok = np.zeros(n_slots, np.int32)
        self._new_count = np.zeros(n_slots, np.int32)
        self._max_new_arr = np.full(n_slots, self.max_new, np.int32)
        self._temp_arr = np.full(n_slots, temperature, np.float32)
        self._keys = np.array(
            jax.random.split(self._base_key, n_slots), np.uint32)
        self._ctrl_dev = None  # device copies of the submit-time-only arrays
        self._slot_dev = None  # device (last_tok, pos, active, new_count),
        # carried across steps; None => re-upload from the host mirrors
        self.results: dict[int, GenerationResult] = {}
        self.slot_req: dict[int, int] = {}
        self._next_req = 0
        self._prefill_fns: dict[int, object] = {}
        self.executor = (
            self._build_executor(artifact, interpret) if use_kernel else None)
        ex = self.executor
        self._decode = jax.jit(
            lambda p, s, t, pos: api.decode(p, cfg, s, t, pos, executor=ex))
        self.step_dispatches = 0  # jitted fused-step invocations (observability)
        # pallas_calls per traced decode step, keyed by input bucket
        # ("BxT"): retraces record under their own key and a warm retrace
        # can only raise a key's value (max), never clobber the cold count
        self._trace_launches: dict[str, int] = {}
        # telemetry: metrics=None -> fresh per-engine registry; metrics=False
        # -> fully off (the A/B baseline for overhead measurement); any
        # MetricsRegistry -> shared.  tracer=True builds a RequestTracer
        # publishing into the same registry; the scheduler reads engine.tracer.
        if metrics is False:
            self.metrics = None
        else:
            self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.profiler = (StepProfiler() if self.metrics is not None
                         else None)
        if tracer is True:
            self.tracer: RequestTracer | None = RequestTracer(
                metrics=self.metrics)
        else:
            self.tracer = tracer or None
        m = self.metrics
        if m is not None:
            # pre-resolved metric objects: the per-step hot path never walks
            # the registry's name table
            self._m_steps = m.counter(
                "serving_decode_steps_total", "fused decode step dispatches")
            self._m_tokens = m.counter(
                "serving_tokens_total", "decode tokens sampled")
            self._m_step_hist = m.histogram(
                "serving_decode_step_seconds",
                "fused decode step wall (host-synced)")
            self._m_prefills = m.counter(
                "serving_prefills_total", "prompt admissions by prefill kind",
                labels=("kind",))
            self._m_prefill_hist = m.histogram(
                "serving_prefill_seconds", "submit() prefill wall")
            self._m_launches = m.gauge(
                "serving_pallas_launches_per_step",
                "Pallas launches in one traced decode step",
                labels=("bucket",))
            self._m_grown = m.counter(
                "serving_blocks_grown_total",
                "KV blocks allocated mid-decode")
            self._m_exhausted = m.counter(
                "serving_pool_exhausted_total",
                "requests errored by KV pool exhaustion")
            self._m_pool = m.gauge(
                "serving_kv_pool", "KV block pool stats", labels=("stat",))
            m.gauge("serving_slots", "decode slots").set(n_slots)
            # host seconds of each serving.* span, less its nested spans'
            # (repro.obs.spans); the scheduler's spans publish here too
            self.phases = m.counter(
                "serving_phase_seconds_total",
                "host wall seconds by program phase (own time)",
                labels=("phase",))
            self._m_prefill_tok = m.counter(
                "serving_prefill_tokens_total",
                "prefill positions: prompt tokens computed, prompt tokens "
                "the prefix cache supplied, bucket padding computed",
                labels=("kind",))
            install_compile_counter()
        else:
            self._m_steps = self._m_tokens = self._m_step_hist = None
            self._m_prefills = self._m_prefill_hist = self._m_launches = None
            self._m_grown = self._m_exhausted = self._m_pool = None
            self.phases = self._m_prefill_tok = None
        self._step_fn = self._build_step_fn()

    def _init_state(self, init_fn):
        """The decode state, built directly in its mesh sharding when
        serving on a mesh (never whole on one device first)."""
        if self.mesh is None:
            return init_fn()
        from repro.distributed import sharding as shd

        return shd.init_sharded(init_fn, self.mesh, shd.decode_state_pspecs)

    @staticmethod
    def _build_executor(artifact, interpret):
        """Site-keyed :class:`CompressedExecutor` over the artifact — family
        agnostic (None when the artifact has no routable sites)."""
        if artifact is None:
            return None
        ex = CompressedExecutor(artifact, interpret=interpret)
        return ex if ex.sites else None

    # ---------------------------------------------------------- fused step
    def _build_step_fn(self):
        """Jit the whole decode step — forward, sampling, bookkeeping — so
        ``step()`` costs one dispatch and one small device->host transfer."""
        cfg, ex, max_len = self.cfg, self.executor, self.max_len

        def fused(params, state, last_tok, pos, active, new_count,
                  max_new, temps, keys, eos):
            # a slot emits only with cache headroom AND budget left (the
            # pre-check makes max_new <= 0 finish without sampling)
            can_emit = (pos < max_len) & (new_count < max_new)
            emit = active & can_emit
            # non-emitting slots feed position -1: one_hot(-1) writes nothing
            # (attention_decode keeps negative positions out of ring caches
            # too), so free/finished slots never scribble on their cache
            toks = jnp.where(emit, last_tok, 0)[:, None]
            dpos = jnp.where(emit, pos - 1, -1).astype(jnp.int32)
            # launch accounting: this body runs at trace time, so the counter
            # delta around api.decode is exactly the pallas_calls one decode
            # step emits.  Record per input bucket and keep each bucket's max:
            # a warm retrace can undercount through inner-jit caches but a
            # real bucket change gets its own honest cold count
            t0 = dispatch.launch_count()
            logits, new_state = api.decode(params, cfg, state, toks, dpos,
                                           executor=ex)
            bucket = f"{toks.shape[0]}x{toks.shape[1]}"
            n_launch = max(dispatch.launch_count() - t0,
                           self._trace_launches.get(bucket, 0))
            self._trace_launches[bucket] = n_launch
            if self._m_launches is not None:
                self._m_launches.set(n_launch, bucket=bucket)
            sub = jax.vmap(jax.random.fold_in)(keys, new_count)
            nxt = api.sample_tokens(logits.astype(jnp.float32), sub, temps)
            nxt = jnp.where(emit, nxt, last_tok)
            pos2 = pos + emit
            count2 = new_count + emit
            done = emit & (((eos >= 0) & (nxt == eos))
                           | (count2 >= max_new) | (pos2 >= max_len))
            done = done | (active & ~can_emit)
            packed = jnp.stack([nxt.astype(jnp.int32), emit.astype(jnp.int32),
                                done.astype(jnp.int32)])
            # carried device ctrl state: mirrors exactly the host-side updates
            # in step(), so the next step needs no H2D re-upload of it
            ctrl = (nxt, pos2, active & ~done, count2)  # nxt already carries
            # last_tok for non-emitting rows
            return new_state, packed, ctrl

        # the previous step's state dies the moment the new one lands, so
        # donate it: the layer loop carries the paged KV pools and scatters
        # each layer's new rows into them, so the new state reuses the
        # donated buffers and no op copies a pool (a described-v5e compile
        # in tests/test_tpu_compile.py reads the optimised step for this)
        if self.mesh is None:
            return jax.jit(fused, donate_argnums=(1,))
        from repro.distributed import sharding as shd

        self._param_sh = shd.named(self.mesh, shd.params_pspecs(self.params, self.mesh))
        self._state_sh = shd.named(self.mesh, shd.decode_state_pspecs(self.state, self.mesh))
        self.params = jax.device_put(self.params, self._param_sh)
        rep = NamedSharding(self.mesh, P())
        # explicit shardings: prefill-time state surgery can't change the step
        # signature, so the step never re-traces on a sharding flip
        return jax.jit(fused,
                       in_shardings=(self._param_sh, self._state_sh) + (rep,) * 8,
                       out_shardings=(self._state_sh, rep, (rep,) * 4),
                       donate_argnums=(1,))

    @property
    def pallas_launches_per_step(self) -> int:
        """Measured Pallas launches in one fused decode step — the max over
        every traced input bucket (0 before the first step traces; excludes
        prefill, which runs dense)."""
        return max(self._trace_launches.values(), default=0)

    # ------------------------------------------------------------------ API
    def validate_prompt(self, prompt: list[int]) -> str | None:
        """Why a prompt cannot be served (None when it can).  Single source of
        truth for ``submit()`` (raises) and the scheduler (errored result)."""
        if not prompt:
            return "empty prompt: decode needs at least one token"
        if len(prompt) > self.max_len:
            return (f"prompt of {len(prompt)} tokens exceeds the engine's "
                    f"max_len={self.max_len} KV cache")
        if (self.pool is not None and not self.pool.windowed
                and self.pool.blocks_for(len(prompt)) + 1 > self.pool.n_blocks):
            return (f"prompt of {len(prompt)} tokens can never fit the KV "
                    f"pool ({self.pool.n_blocks} blocks of "
                    f"{self.pool.block_size} tokens, one reserved for decode)")
        return None

    def can_admit(self, prompt: list[int]) -> bool:
        """Whether ``submit(prompt)`` would succeed *right now*: a free slot,
        and (paged) enough free or evictable blocks after prefix sharing.
        The scheduler's continuous-batching gate."""
        if self.active.all():
            return False
        return self.pool is None or self.pool.can_admit(prompt)

    def pool_stats(self) -> dict:
        """KV-pool telemetry.  Always the full key set — contiguous engines
        report every key zeroed (``n_blocks == 0`` distinguishes them) so
        callers never branch on engine kind.  Mirrored into the registry's
        ``serving_kv_pool{stat=...}`` gauge when metrics are enabled."""
        s = empty_stats() if self.pool is None else self.pool.stats()
        if self._m_pool is not None:
            for k, v in s.items():
                self._m_pool.set(v, stat=k)
        return s

    def submit(self, prompt: list[int], *, max_new: int | None = None,
               temperature: float | None = None,
               sid: int | None = None) -> int:
        """Prefill a prompt into a free slot; returns request id.

        ``max_new`` / ``temperature`` override the engine defaults for this
        request only (the per-slot budget/temp arrays feed the fused step).
        ``sid`` (the request tracer's span id, where the caller has one) is
        carried on the admission's profiler spans beside the request id.
        """
        err = self.validate_prompt(prompt)
        if err is not None:
            raise ValueError(err)
        free = np.where(~self.active)[0]
        if free.size == 0:
            raise RuntimeError("no free slots; call step() until one finishes")
        slot = int(free[0])
        rid = self._next_req
        self._next_req += 1
        ids = {"rid": rid} if sid is None else {"rid": rid, "sid": sid}
        with span("serving.submit", phases=self.phases, slot=slot,
                  prompt_len=len(prompt), **ids) as sp:
            t_pre = time.perf_counter()
            cached_tokens = 0
            kind = "paged" if self.paged else (
                "bulk" if self.bulk_prefill
                and ("k" in self.state or "c_kv" in self.state)
                else "tokenwise")
            if self.paged:
                with span("serving.submit.pool", phases=self.phases, **ids):
                    plan = self.pool.admit(slot, prompt)
                if plan is None:
                    self._next_req -= 1
                    raise RuntimeError(
                        f"insufficient free KV blocks for a {len(prompt)}-"
                        f"token prompt ({self.pool.available_blocks} "
                        "available); step() until a request finishes")
                sp.set(cached=plan.cached_tokens)
                self._prefill_slot_paged(slot, prompt, plan, ids)
                with span("serving.submit.pool", phases=self.phases, **ids):
                    self.pool.register_prefix(slot, prompt)
                cached_tokens = plan.cached_tokens
            elif kind == "bulk":
                # one bulk forward writes the whole slot cache (and rewrites
                # the full kpos row, so stale entries need no separate reset)
                self._prefill_slot(slot, prompt, ids)
            else:
                # stateful families (ssm/hybrid) keep the tokenwise path:
                # their per-layer recurrent states live in scan-stacked
                # layouts that a bulk forward does not expose per-slot; the
                # slot column is reset first so the previous occupant's
                # state/kpos never leaks
                self._reset_slot_state(slot)
                with span("serving.submit.prefill", phases=self.phases, **ids):
                    self._prefill_slot_tokenwise(slot, prompt)
                self._count_prefill(len(prompt), 0, 0)
            self.pos[slot] = len(prompt)
            self.active[slot] = True
            self._last_tok[slot] = prompt[-1]
            self._new_count[slot] = 0
            self._max_new_arr[slot] = (self.max_new if max_new is None
                                       else max_new)
            self._temp_arr[slot] = (self.temp if temperature is None
                                    else temperature)
            # request-keyed PRNG: draws depend on (seed, rid, step), never on
            # which slot the request landed in or what else is in flight
            self._keys[slot] = np.asarray(
                jax.random.fold_in(self._base_key, rid), np.uint32)
            self._ctrl_dev = None  # budget/temp/keys changed: re-upload once
            self._slot_dev = None  # host mirrors mutated: re-upload once
            self.slot_req[slot] = rid
            # host wall of the whole admission (dispatch + bookkeeping; the
            # device work may still be in flight — bench paths that want the
            # synced latency block on eng.state themselves)
            prefill_s = time.perf_counter() - t_pre
            if self._m_prefills is not None:
                self._m_prefills.inc(1, kind=kind)
                self._m_prefill_hist.observe(prefill_s)
            self.results[rid] = GenerationResult(
                tokens=list(prompt), prompt_len=len(prompt), finished=False,
                stats={"prefill_s": prefill_s, "prefill_kind": kind,
                       "cached_tokens": cached_tokens})
            return rid

    # -------------------------------------------------------------- prefill
    def _reset_slot_state(self, slot: int) -> None:
        """Clear one slot's column of every decode-state leaf (kpos-style
        position maps to -1, caches/recurrent states to 0) so a reused slot
        never sees its previous occupant's KV entries or SSM state."""
        st = dict(self.state)
        for name, v in st.items():
            if name.startswith("cross_"):
                continue  # whisper cross-KV is set per slot by the caller
            fill = -1 if "kpos" in name else 0
            st[name] = v.at[:, slot].set(jnp.asarray(fill, v.dtype))
        self.state = st

    def _merge_slot_state(self, old, new, slot: int):
        """Take ``new``'s batch column ``slot``, keep ``old`` elsewhere — the
        tokenwise prefill must not advance other slots' recurrent state."""
        return jax.tree.map(lambda o, n: o.at[:, slot].set(n[:, slot]),
                            old, new)

    def _prefill_slot_tokenwise(self, slot: int, prompt: list[int]) -> None:
        """Legacy prefill: one decode step per prompt token (kept as the
        fallback for recurrent-state families and as the bulk path's
        equivalence/latency baseline in benchmarks).  Decode rows are
        independent, so the loop runs on a scratch state and only the target
        slot's column is merged back — other slots never see the prefill."""
        old = scratch = self.state
        for t, tok in enumerate(prompt):
            _logits, scratch = self._decode(
                self.params, scratch,
                self._token_batch(slot, tok), self._pos_batch(slot, t))
        self.state = self._merge_slot_state(old, scratch, slot)

    def _count_prefill(self, prompt: int, cached: int, pad: int) -> None:
        """One admission's prefill positions: prompt tokens the forward
        computes, prompt tokens the prefix cache supplied, bucket padding."""
        if self._m_prefill_tok is not None:
            self._m_prefill_tok.inc(prompt, kind="prompt")
            self._m_prefill_tok.inc(cached, kind="cached")
            self._m_prefill_tok.inc(pad, kind="pad")

    def _prefill_slot(self, slot: int, prompt: list[int], ids: dict) -> None:
        """Bulk prefill: ONE ``api.prefill`` forward over the prompt writes
        the slot's KV cache at its positions.  Prompts are right-padded to
        power-of-two buckets so recompilation is bounded (log2(max_len)
        buckets); padded positions stay masked via kpos == -1."""
        plen = len(prompt)
        s_pad = min(self.max_len, max(8, 1 << (plen - 1).bit_length()))
        if s_pad not in self._prefill_fns:
            cfg = self.cfg
            self._prefill_fns[s_pad] = jax.jit(
                lambda p, t: api.prefill(p, cfg, {"tokens": t},
                                         collect_cache=True))
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :plen] = prompt
        with span("serving.submit.prefill", phases=self.phases, **ids):
            _h, caches = self._prefill_fns[s_pad](self.params,
                                                  jnp.asarray(toks))
        self._count_prefill(plen, 0, s_pad - plen)
        st = dict(self.state)
        if "k" in st:
            k_all, v_all = caches  # [L, 1, S_pad, Hkv, Dh]
            eff = st["k"].shape[2]  # ring size when windowed, else max_len
            ps = np.arange(max(0, plen - eff), plen)
            slots = ps % eff if self.cfg.attn_window is not None else ps
            kpos_row = np.full(eff, -1, np.int64)
            kpos_row[slots] = ps
            st["k"] = st["k"].at[:, slot, slots].set(
                k_all[:, 0, ps].astype(st["k"].dtype))
            st["v"] = st["v"].at[:, slot, slots].set(
                v_all[:, 0, ps].astype(st["v"].dtype))
        else:  # MLA latent cache
            c_kv, k_rope = caches  # [L, 1, S_pad, dc] / [L, 1, S_pad, Dr]
            eff = st["c_kv"].shape[2]
            ps = np.arange(plen)
            kpos_row = np.full(eff, -1, np.int64)
            kpos_row[:plen] = ps
            st["c_kv"] = st["c_kv"].at[:, slot, :plen].set(
                c_kv[:, 0, :plen].astype(st["c_kv"].dtype))
            st["k_rope"] = st["k_rope"].at[:, slot, :plen].set(
                k_rope[:, 0, :plen].astype(st["k_rope"].dtype))
        st["kpos"] = st["kpos"].at[:, slot].set(jnp.asarray(kpos_row, jnp.int32))
        self.state = st

    # --------------------------------------------------------- paged prefill
    def _scatter_pool(self, st, name, tbl_row, vidx, vals):
        """Write per-token values ``vals`` [L, n, ...] into the pool at the
        slot's logical view indices ``vidx`` (block = table[v // bs], offset
        v % bs) — one scatter dispatch per leaf."""
        bs = self.pool.block_size
        blocks = tbl_row[vidx // bs]
        offs = vidx % bs
        st[name] = st[name].at[:, blocks, offs].set(vals.astype(st[name].dtype))

    def _prefill_slot_paged(self, slot: int, prompt: list[int], plan,
                            ids: dict) -> None:
        """Apply an :class:`~repro.serving.kvpool.AdmitPlan`: install the
        block table row, device-copy the COW block, prefill only the
        non-cached tail (bulk forward when cold, ``api.prefill_extend``
        against the gathered resident prefix on a prefix hit), and scatter
        the fresh K/V into the slot's blocks."""
        st = dict(self.state)
        cfg, pool = self.cfg, self.pool
        bs, plen = pool.block_size, len(prompt)
        view = pool.view_blocks * bs  # == ring size when windowed
        tbl_row = plan.table
        with span("serving.submit.pool", phases=self.phases, **ids):
            self._tbl_host[slot] = tbl_row
            st["block_tbl"] = jnp.asarray(self._tbl_host)
        if plan.cow is not None:
            src, dst = plan.cow
            with span("serving.submit.scatter", phases=self.phases, **ids):
                for name in self._pool_leaves:
                    st[name] = st[name].at[:, dst].set(st[name][:, src])
        cached = plan.cached_tokens
        kpos_row = np.full(view, -1, np.int64)
        if cfg.attn_window is not None:  # ring layout, no prefix sharing
            ps = np.arange(max(0, plen - view), plen)
            vidx = ps % view
            kpos_row[vidx] = ps
        else:
            ps = np.arange(cached, plen)
            vidx = ps
            kpos_row[:plen] = np.arange(plen)
        if not ps.size:  # cached == plen: nothing to prefill — the first
            # decode step recomputes the last token's K/V anyway
            self._count_prefill(0, cached, 0)
        elif cached == 0:
            s_pad = min(self.max_len, max(8, 1 << (plen - 1).bit_length()))
            if s_pad not in self._prefill_fns:
                self._prefill_fns[s_pad] = jax.jit(
                    lambda p, t: api.prefill(p, cfg, {"tokens": t},
                                             collect_cache=True))
            toks = np.zeros((1, s_pad), np.int32)
            toks[0, :plen] = prompt
            with span("serving.submit.prefill", phases=self.phases, **ids):
                _h, caches = self._prefill_fns[s_pad](self.params,
                                                      jnp.asarray(toks))
            self._count_prefill(plen, 0, s_pad - plen)
            with span("serving.submit.scatter", phases=self.phases, **ids):
                for name, c_all in zip(self._pool_leaves, caches):
                    self._scatter_pool(st, name, tbl_row, vidx,
                                       c_all[:, 0, ps])
        else:
            self._extend_tail(st, prompt, cached, tbl_row, vidx, view, ids)
        with span("serving.submit.scatter", phases=self.phases, **ids):
            st["kpos"] = st["kpos"].at[:, slot].set(
                jnp.asarray(kpos_row, jnp.int32))
        self.state = st

    def _extend_tail(self, st, prompt, cached, tbl_row, vidx, view,
                     ids: dict) -> None:
        """Prefix-hit tail prefill: gather the resident prefix through the
        block table (the exact contiguous view), run the tail tokens against
        it in one bucketed jitted forward, scatter the tail K/V back."""
        cfg, plen = self.cfg, len(prompt)
        tl = plen - cached
        t_pad = max(8, 1 << (tl - 1).bit_length())
        if t_pad not in self._extend_fns:
            self._extend_fns[t_pad] = jax.jit(
                lambda p, t, pos, past, last: api.prefill_extend(
                    p, cfg, t, pos, past, last))
        toks = np.zeros((1, t_pad), np.int32)
        toks[0, :tl] = prompt[cached:]
        posn = np.full((1, t_pad), -1, np.int64)
        posn[0, :tl] = np.arange(cached, plen)
        with span("serving.submit.prefill", phases=self.phases, **ids):
            past = {}
            for name in self._pool_leaves:
                pool_leaf = st[name]  # [L, Nb, bs, ...]
                g = pool_leaf[:, tbl_row]  # gather: [L, mb, bs, ...]
                past[name] = g.reshape(pool_leaf.shape[0], 1, view,
                                       *pool_leaf.shape[3:])
            pk = np.full((1, view), -1, np.int64)
            pk[0, :cached] = np.arange(cached)
            past["kpos"] = jnp.broadcast_to(
                jnp.asarray(pk, jnp.int32)[None], (cfg.n_layers, 1, view))
            _logits, tails = self._extend_fns[t_pad](
                self.params, jnp.asarray(toks), jnp.asarray(posn, jnp.int32),
                past, jnp.asarray([tl - 1], jnp.int32))
        self._count_prefill(tl, cached, t_pad - tl)
        with span("serving.submit.scatter", phases=self.phases, **ids):
            for name, tail in tails.items():  # [L, 1, t_pad, ...]
                self._scatter_pool(st, name, tbl_row, vidx, tail[:, 0, :tl])

    def _release_slot(self, slot: int) -> None:
        """Return a retired slot's blocks to the pool (registered prefix
        blocks stay cached) and clear its table row."""
        self.pool.release(slot)
        self._tbl_host[slot] = 0
        self.state = {**self.state,
                      "block_tbl": jnp.asarray(self._tbl_host)}

    def cancel(self, rid: int) -> bool:
        """Stop an in-flight request (its slot frees on the spot); returns
        whether anything was cancelled.  The result keeps the tokens sampled
        so far and is marked finished."""
        for slot, r in self.slot_req.items():
            if r == rid and self.active[slot]:
                self.active[slot] = False
                self._slot_dev = None  # host mirrors mutated: re-upload once
                if self.paged:
                    self._release_slot(slot)
                self.results[rid].finished = True
                self.results[rid].stats["cancelled"] = True
                return True
        return False

    def step(self) -> list[StepEvent]:
        """One fused decode step for every active slot: exactly one jitted
        dispatch; the only device->host traffic is the packed [3, n_slots]
        (token, emit, done) array.  Returns this step's per-slot events."""
        if not self.active.any():
            return []
        with span("serving.step", phases=self.phases,
                  step_num=self.step_dispatches):
            return self._step()

    def _step(self) -> list[StepEvent]:
        events: list[StepEvent] = []
        if self.paged:
            with span("serving.step.grow", phases=self.phases):
                events.extend(self._grow_blocks())
            if not self.active.any():
                return events
        eos = np.int32(-1 if self.eos is None else self.eos)
        if self._ctrl_dev is None or self._slot_dev is None:
            with span("serving.step.upload", phases=self.phases):
                if self._ctrl_dev is None:  # max_new/temps/keys: at submit
                    self._ctrl_dev = (jnp.asarray(self._max_new_arr),
                                      jnp.asarray(self._temp_arr),
                                      jnp.asarray(self._keys))
                if self._slot_dev is None:  # first step after a host mutation
                    self._slot_dev = (
                        jnp.asarray(self._last_tok),
                        jnp.asarray(self.pos, jnp.int32),
                        jnp.asarray(self.active),
                        jnp.asarray(self._new_count))
        max_new_d, temps_d, keys_d = self._ctrl_dev
        t0 = self.profiler.begin() if self.profiler is not None else 0.0
        with span("serving.step.dispatch", phases=self.phases):
            new_state, packed, self._slot_dev = self._step_fn(
                self.params, self.state, *self._slot_dev,
                max_new_d, temps_d, keys_d, eos)
        self.step_dispatches += 1
        self.state = new_state
        with span("serving.step.wait", phases=self.phases):
            nxt, emit, done = np.asarray(packed)  # the one small host transfer
        if self.profiler is not None:
            # np.asarray above already synced the step
            n_emit = int(emit.sum())
            dt = self.profiler.end(t0, tokens=n_emit)
            self._m_steps.inc()
            self._m_tokens.inc(n_emit)
            self._m_step_hist.observe(dt)
        with span("serving.step.events", phases=self.phases):
            for slot in np.where(self.active)[0]:
                rid = self.slot_req[slot]
                r = self.results[rid]
                tok: int | None = None
                if emit[slot]:
                    tok = int(nxt[slot])
                    r.tokens.append(tok)
                    self._last_tok[slot] = tok
                    self.pos[slot] += 1
                    self._new_count[slot] += 1
                if done[slot]:
                    r.finished = True
                    self.active[slot] = False
                    if self.paged:
                        with span("serving.step.release", phases=self.phases,
                                  rid=rid, slot=int(slot)):
                            self._release_slot(slot)
                events.append(StepEvent(rid=rid, token=tok,
                                        finished=bool(done[slot])))
        return events

    def _grow_blocks(self) -> list[StepEvent]:
        """Pre-step block growth: the upcoming step writes each active slot's
        K/V at view index ``pos - 1`` — allocate the covering block when the
        table has none (0 = null).  Windowed slots preallocate their whole
        ring at admit, so this is a no-op for them.  A slot the pool cannot
        grow finishes with an error (its blocks return to the pool)."""
        events: list[StepEvent] = []
        bs = self.pool.block_size
        view = self.pool.view_blocks * bs
        dirty = False
        for slot in np.where(self.active)[0]:
            bi = (int(self.pos[slot]) - 1) % view // bs
            if self._tbl_host[slot, bi] != 0:
                continue
            bid = self.pool.append_block(slot)
            if bid is None:
                rid = self.slot_req[slot]
                r = self.results[rid]
                r.finished = True
                r.error = ("KV block pool exhausted mid-decode "
                           f"({self.pool.in_use_blocks} blocks in use)")
                r.stats["exhausted"] = True
                if self._m_exhausted is not None:
                    self._m_exhausted.inc()
                self.active[slot] = False
                self._slot_dev = None
                with span("serving.step.release", phases=self.phases,
                          rid=rid, slot=int(slot)):
                    self._release_slot(slot)
                events.append(StepEvent(rid=rid, token=None, finished=True))
                continue
            self._tbl_host[slot, bi] = bid
            r = self.results[self.slot_req[slot]]
            r.stats["blocks_grown"] = r.stats.get("blocks_grown", 0) + 1
            if self._m_grown is not None:
                self._m_grown.inc()
            dirty = True
        if dirty:
            self.state = {**self.state,
                          "block_tbl": jnp.asarray(self._tbl_host)}
        return events

    def generate(self, prompts: list[list[int]], max_new_tokens: int = 32, *,
                 temperature: float | None = None, on_token=None
                 ) -> list[GenerationResult]:
        """Continuous-batched generation over a request list (Scheduler-driven).

        Invalid prompts (empty / beyond the KV cache) do not abort the batch:
        they come back as ``GenerationResult(finished=True, error=...)`` while
        the rest of the batch completes.  ``on_token(rid, token)`` streams
        tokens as they are sampled.
        """
        from .scheduler import Scheduler

        sched = Scheduler(self)
        rids = [sched.enqueue(p, max_new=max_new_tokens, temperature=temperature,
                              on_token=on_token) for p in prompts]
        sched.run()
        return [sched.take_result(r) for r in rids]

    # -------------------------------------------------------------- helpers
    def _token_batch(self, slot: int, tok: int):
        t = np.zeros((self.n_slots, 1), np.int32)
        t[slot, 0] = tok
        return jnp.asarray(t)

    def _pos_batch(self, slot: int, pos: int):
        p = np.asarray(self.pos - 1, np.int64).clip(0)
        p[slot] = pos
        return jnp.asarray(p, jnp.int32)


# ---------------------------------------------------------------- compression


def compress_ffn_for_serving(params, cfg: ArchConfig, compression=None, *,
                             report=None, interpret: bool | None = None,
                             build_matvecs: bool = True):
    """Legacy FFN-only wrapper over :func:`models.api.compress_model`.

    Returns ``(params_c, matvecs, report)`` for the FFN projections of a
    dense-FFN transformer: ``params_c`` are the full params with FFN weights
    replaced by their compressed dense equivalent, ``matvecs[proj][layer]``
    the :class:`LCCMatvec` kernels (built through
    :func:`~repro.serving.executor.matvecs_from_artifact`).  Every family —
    and every non-FFN site — is served via ``api.compress_model`` +
    ``ServingEngine(artifact=...)`` directly.
    """
    from repro import core

    if cfg.moe is not None or cfg.family in ("ssm", "hybrid") or cfg.enc_layers:
        raise ValueError(
            f"compress_ffn_for_serving wraps the dense-FFN fast path; family "
            f"{cfg.family!r} is served via models.api.compress_model(...) and "
            "ServingEngine(artifact=...)")
    if compression is None:
        compression = core.CompressionConfig(algorithm="fs", weight_sharing=True,
                                             max_share_rel_err=0.06)
    art = api.compress_model(params, cfg, compression, include="ffn.",
                             build_packed=build_matvecs)
    if report is not None:
        for lc in art.report.layers:
            report.add(lc)
    matvecs: dict[str, list[LCCMatvec]] = {}
    if build_matvecs:
        table = matvecs_from_artifact(art, include="ffn.", interpret=interpret)
        for proj in ("gate", "up", "down"):
            matvecs[proj] = [table[f"ffn.{proj}.l{li}"]
                             for li in range(cfg.n_layers)]
    return art.params, matvecs, art.report if report is None else report
