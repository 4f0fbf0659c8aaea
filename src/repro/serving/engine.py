"""Continuous-batching serving engine: the step-loop driver.

One :class:`ServingEngine` owns the compiled step functions (slot-sliced
chunked prefill + activity-masked decode, launch/steps.py), the serving
caches, the device block pool, the swap-tier paged store, and the scheduler.
Each ``step()``:

1. asks the scheduler for a :class:`StepPlan` at the current clock,
2. executes preemptions (swap-out copy / recompute requeue), resumes
   (swap-in copy) and admissions (chunked prefill; the prefill's last
   logits yield the request's **first generated token**, so TTFT is stamped
   here),
3. runs a fixed-shape decode over every slot with the activity mask —
   either one ``[B_slots, 1]`` step (``horizon=1``, the parity baseline) or
   a **horizon-batched** dispatch (``horizon>1``): the scheduler grants the
   largest safe number of lockstep steps (``grant_horizon``), pre-extends
   block tables for all of them, and one compiled ``lax.scan`` generates up
   to ``h`` tokens per slot on-device, feeding each sampled token back as
   the next input and freezing slots mid-horizon at EOS or budget
   exhaustion.  The host pays ONE dispatch and ONE sync per horizon instead
   of per token — emitted tokens get interpolated timestamps — then appends
   tokens, retires finished requests, and frees their slots/blocks for the
   next step's admissions.

For paged-capable attention families (non-windowed GQA) the device block
pool IS the physical KV store: the caches hold ``k_pool/v_pool`` block
arrays, the engine mirrors every running request's block table into a
``[slots, n_pages]`` device array each step, prefill writes blocks directly,
decode attends through the Pallas paged kernel, and swap-preemption is a
block-to-block copy keyed by table ids instead of an O(max_len) slot-row
scatter.  MLA and sliding-window families keep their dense/ring live caches
behind the same block accounting.

Everything runs at fixed ``[B_slots, S_max]`` / ``[B_slots, 1]`` shapes, so
one compiled executable serves every request mix; only distinct prefill
chunk lengths trace separately (bounded by the workload's length buckets).

Sampling: ``temperature > 0`` switches the decode step (and the prefill's
first token) from greedy argmax to temperature + top-k sampling with
per-slot PRNG keys folded from ``sample_seed`` and the decode step counter.
Greedy (the default) keeps the preemption-parity guarantee; sampled streams
are deterministic for a fixed seed and schedule.

Execution modes follow ``OdinConfig``: ``odin_mode="exact"`` runs the exact
matmuls, ``"int8"`` the ODIN fixed-8-bit expected-value surrogate, ``"sc"``
the bit-parallel stochastic kernels (slow; reference).
"""
from __future__ import annotations

import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.steps import (init_serving_caches,
                                make_serving_decode_guarded,
                                make_serving_decode_horizon,
                                make_serving_decode_step,
                                make_serving_mixed_step,
                                make_serving_spec_horizon,
                                make_slot_prefill_step, pageable_block,
                                speculable)
from repro.models import lm
from repro.nn import module as nnmod
from repro.nn.attention import POOL_LEAVES
from repro.serving.blocks import (SEQ_LEAVES, BlockPool, PagedKVStore,
                                  _leaf_name)
from repro.serving.degrade import DegradationController, DegradeConfig
from repro.serving.faults import (EngineStallError, FaultPlan, ShuttingDown,
                                  SwapCopyError)
from repro.serving.metrics import EngineStats, OdinCostModel, summarize
from repro.serving.reliability import ReliabilityConfig
from repro.serving.scheduler import (PrefixCache, PrefixGrant, Request,
                                     RequestState, Scheduler)
from repro.serving.trace import NULL_TRACER, MetricsRegistry

__all__ = ["ServingEngine"]

# host phase → the EngineStats field its seconds go to; every compiled
# dispatch's own phase (``mixed``, ``decode``, …) goes to dispatch_launch_s
_PHASE_FIELDS = {"plan": "host_plan_s", "pack": "host_pack_s",
                 "tables": "host_tables_s", "sync": "dispatch_sync_s",
                 "wear": "host_wear_s", "emit": "host_emit_s",
                 "deliver": "deliver_s", "idle": "idle_wait_s"}


class _HostPhase:
    """``with engine._phase("plan"):`` — one host phase of the loop.

    Always adds the phase's own seconds on the engine clock to its
    ``EngineStats`` field: a phase nested inside it and any garbage
    collection counted inside it are taken out, so no second counts twice.
    The tracer's span of the same name (a shared no-op with tracing off)
    opens and closes with it."""

    __slots__ = ("eng", "name", "field", "t0", "mark", "span")

    def __init__(self, eng: "ServingEngine", name: str):
        self.eng = eng
        self.name = name
        self.field = _PHASE_FIELDS.get(name, "dispatch_launch_s")

    def __enter__(self):
        eng = self.eng
        self.span = eng.tracer.phase(self.name)
        self.span.__enter__()
        self.mark = eng._phase_spent + eng.stats.gc_pause_s
        self.t0 = eng._now()
        return self

    def __exit__(self, *exc) -> None:
        eng = self.eng
        st = eng.stats
        inner = eng._phase_spent + st.gc_pause_s - self.mark
        dt = max(0.0, eng._now() - self.t0 - inner)
        setattr(st, self.field, getattr(st, self.field) + dt)
        eng._phase_spent += dt
        self.span.__exit__(*exc)


class ServingEngine:
    """Drives continuous-batching inference over ``slots`` cache slots.

    Parameters
    ----------
    cfg : ModelConfig (smoke or full).
    slots : decode batch width B (one compiled ``[B, 1]`` decode step).
    max_len : per-slot cache depth; every request needs prompt+max_new ≤ max_len.
    block_size : KV block granularity (max_len must divide evenly).
    n_blocks : device KV budget in blocks.  Default ``slots·max_len/block_size``
        (never preempts); set lower to exercise preemption under load.
    swap_blocks : swap-tier capacity in blocks (0 disables swap — preemption
        falls back to recompute).
    prefill_chunk : chunked-prefill granularity (default: max_len, i.e. one
        chunk).  Smaller chunks bound the prefill executable's shape.
    paged : use the paged physical KV store for paged-capable attention
        families (non-windowed GQA).  ``False`` keeps the PR-1 dense
        ``[slots, max_len]`` live caches everywhere (the benchmark baseline).
    prefix_sharing : dedup identical prompt prefixes across requests via
        refcounted block aliasing + copy-on-write forks (scheduler
        PrefixCache): admissions alias resident prefix blocks and prefill
        only the unmatched tail.  ``None`` (default) enables it exactly when
        the whole model state is paged — every cache leaf lives in the block
        pool (non-windowed GQA stacks); MLA / sliding-window / recurrent
        families keep per-slot dense state a shared block cannot cover, so
        sharing silently stays off.  ``True`` raises if the model is not
        fully paged; requests carrying ``extras`` (vision patch embeddings —
        KV not token-determined) always bypass matching and registration.
    horizon : max decode steps fused into one dispatch.  1 (default) is the
        single-step parity baseline; >1 asks ``Scheduler.grant_horizon`` for
        the largest safe power-of-two grant each step and runs the fused
        on-device loop.  Greedy token streams are identical for every
        horizon; sampled streams match whenever the slot schedule does (the
        per-step key folds the *global* decode-step counter either way).
    spec_ngram : draft length K for n-gram self-speculative decode (0
        disables).  Each horizon inner step drafts K tokens by prompt-lookup
        over the slot's on-device token history, verifies all K+1 logits in
        ONE forward through the multi-token-query paged kernel, emits the
        longest accepted prefix plus the bonus token (1..K+1 tokens per
        inner step — every one a greedy argmax, so spec-on streams are
        token-identical to spec-off by construction), and rolls rejected KV
        rows back by not advancing the slot's length.  Greedy only
        (temperature must be 0); requires every cache leaf to be
        position-addressed (no SSM/xLSTM recurrent state) and a
        single-codebook vocabulary — ``speculable(cfg)``.
    spec_hist : token-history window for the n-gram draft match (per slot,
        device-resident; seeded from the prompt tail at admission).
    mixed : fused mixed prefill+decode dispatch (chunked-prefill
        piggybacking, à la Sarathi/vLLM).  While any slot is mid-prompt, ONE
        dispatch carries [decode slots at q_len = 1] + [prefill slots at
        q_len = chunk-or-less], packed by ``Scheduler.pack_mixed`` under
        ``mixed_budget`` total query rows — running streams keep emitting
        every dispatch instead of stalling behind an admission's prefill
        loop, which is what makes steady-state TPOT independent of arrival
        bursts.  ``None`` (default) enables it exactly when the whole model
        state is paged (same gate as prefix sharing: the mixed tile writes
        KV through the block tables, so per-slot dense/recurrent state
        cannot ride along); ``True`` raises if the model is not fully
        paged; ``False`` keeps the separate alternating prefill/decode
        paths (the ``--no-mixed`` baseline).  Greedy mixed-on streams are
        token-identical to mixed-off: each emitted token is still the
        argmax at the same position over the same KV (requests carrying
        ``extras`` always take the separate single-chunk prefill).
    mixed_budget : total query rows per mixed dispatch (default
        ``prefill_chunk + slots``: every decode slot rides along at full
        chunk-rate prefill progress).  Decode rows are packed first; one
        row is always reserved for the oldest mid-prefill slot.  It also
        fixes the mixed program's prefill lanes, ``max(1, (mixed_budget -
        slots) // prefill_chunk)`` (1 at the default): at most that many
        prompts progress per dispatch, each in a lane of its own.
    jit_cache : max fused decode executables kept compiled (LRU over
        (horizon, spec) grants; evictions counted in ``EngineStats``).
    jit_cache : max fused decode executables kept compiled (LRU over
        (horizon, spec) grants; evictions counted in ``EngineStats``).
    eos_id : token id that ends a request early (None disables; multi-
        codebook models match on the first codebook).  Checked on-device
        inside horizons and host-side everywhere else.
    temperature / top_k / sample_seed : decode sampling (0 ⇒ greedy argmax).
        Sampled streams are deterministic for a fixed seed and schedule, but
        NOT preemption-invariant (a resume re-enters the per-step key
        stream); greedy keeps the token-stream parity guarantee.
    odin_mode : override cfg.odin_mode ("exact" | "int8" | "sc").
    on_token : streaming callback ``(request, token, t_now)`` per emitted
        token.  Inside a horizon, per-token timestamps are interpolated
        across the dispatch's wall time (TTFT from prefill stays exact).
    clock : monotonic seconds callable (injectable for deterministic tests).
    tracer : a :class:`repro.serving.trace.Tracer` to record dispatch spans,
        request lifecycle flows and scheduler/pool decision events into
        (exportable as Perfetto-loadable Chrome trace JSON).  Default None ⇒
        the no-op recorder: every emit site is guarded by ``tracer.enabled``,
        so the trace-off hot path builds no event, and each host phase
        costs two clock reads and a float add (``EngineStats.host_*_s``,
        ``dispatch_*_s``, always on).  A tracer also counts garbage
        collections (``gc_pause_s``, ``gc_collections``) while attached.
    metrics_window : window length (engine-clock seconds) for the windowed
        metrics registry — TTFT/TPOT/dispatch-wall-time histograms and
        counter deltas are snapshotted per window so long runs report
        p50/p99 over time (``summary()["metrics"]["windows"]``).
    xla_annotations : with a ``tracer``, each host phase of the loop also
        opens a ``jax.profiler.TraceAnnotation`` named ``serving/<phase>``
        (``plan``, ``pack``, ``tables``, the dispatch's kind, ``sync``,
        ``wear``, ``emit``; ``deliver``/``idle`` in the front door; ``gc``),
        so XLA profiler timelines say what the host was doing between and
        inside dispatches.  Without a tracer nothing is annotated.
    deadline_s / queue_timeout_s : engine-wide defaults stamped onto every
        submitted request that does not carry its own ``deadline`` /
        ``queue_timeout``.  A past-deadline request is released as
        ``TIMEOUT`` at the next step boundary from ANY live state (queued,
        swapped, or running mid-horizon — ``grant_horizon`` additionally
        caps horizons at the earliest running deadline so a fused dispatch
        never burns a full grant of dead work); ``queue_timeout`` is
        relative to arrival and applies only while the request has never
        been admitted.  Requests without lifecycle fields are never
        scanned — the guards-off hot path pays nothing.
    fault_plan : a :class:`repro.serving.faults.FaultPlan` to replay —
        deterministic fault events consumed at the top of each step
        (allocation failures, swap-copy faults, NaN-poisoned logits, clock
        skew).  The engine *contains* every injected fault: no event may
        escape ``step()`` as an exception.  Test/bench-only.
    nan_guard : route fault-step decodes through the guarded executable
        that flags non-finite per-slot logits; a flagged slot's request is
        quarantined as ``FAILED`` ("nan_logits") while co-batched slots
        keep bit-identical streams.  Default None ⇒ enabled exactly when a
        ``fault_plan`` is attached.
    degrade : graceful-degradation controller — True (default thresholds),
        a :class:`~repro.serving.degrade.DegradeConfig`, or a ready
        :class:`~repro.serving.degrade.DegradationController`.  Watches
        pool occupancy / arrived queue depth / preemption churn /
        ``accept_rate`` each step and sheds load along the traced ladder
        (speculation off → horizon shrunk → prefix retention released →
        admission denial with structured retry-after), restoring in
        reverse under hysteresis.  None disables (no per-step cost).
    reliability : PCRAM reliability layer — ``True`` for defaults
        (wear-leveled allocation, no endurance budget, no scrub), a
        :class:`~repro.serving.reliability.ReliabilityConfig` for full
        control, or None/False (off).  Per-block write-endurance accounting
        in the pool is always on (host-side bookkeeping); with a config
        attached the engine additionally wear-levels allocation, drains and
        retires blocks that cross the endurance budget (or are hit by a
        ``stuck_at`` fault), and runs the drift-refresh scrubber — all via
        block copies of identical bytes, so greedy streams stay
        bit-identical with reliability on vs. off.
    """

    def __init__(self, cfg: ModelConfig, *, slots: int, max_len: int,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 swap_blocks: int = 0, prefill_chunk: Optional[int] = None,
                 paged: bool = True, prefix_sharing: Optional[bool] = None,
                 mixed: Optional[bool] = None,
                 mixed_budget: Optional[int] = None,
                 horizon: int = 1, spec_ngram: int = 0, spec_hist: int = 64,
                 jit_cache: int = 8,
                 eos_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0,
                 params=None, seed: int = 0, odin_mode: Optional[str] = None,
                 on_token: Optional[Callable] = None,
                 clock: Optional[Callable[[], float]] = None,
                 attribution_cfg: Optional[ModelConfig] = None,
                 tracer=None, metrics_window: float = 1.0,
                 xla_annotations: bool = False,
                 deadline_s: Optional[float] = None,
                 queue_timeout_s: Optional[float] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 nan_guard: Optional[bool] = None,
                 degrade=None,
                 reliability=None):
        if odin_mode is not None:
            cfg = cfg.with_overrides(odin_mode=odin_mode)
        if max_len % block_size:
            raise ValueError(f"max_len {max_len} not divisible by block_size {block_size}")
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.block_size = block_size
        self.n_pages = max_len // block_size
        # Default chunk is bounded: serving prefill routes MoE drop-free, so
        # its expert dispatch buffer scales with the chunk's token count —
        # an unbounded max_len default would pay [E, max_len, d] per layer on
        # full configs.  Drop-free routing is chunk-invariant, so chunking
        # never changes results.
        self.chunk = prefill_chunk or min(max_len, 512)
        if params is None:
            params = nnmod.materialize(lm.param_spec(cfg), jax.random.PRNGKey(seed))
        self.params = params
        self.on_token = on_token
        self._clock = clock or time.monotonic
        self._t0: Optional[float] = None
        # clock-skew fault state: an injected offset plus a monotone clamp
        # (a negative skew must never run the engine clock backwards —
        # timestamps, windows and deadlines all assume monotonicity)
        self._skew = 0.0
        self._last_now = 0.0
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.sample_seed = int(sample_seed)
        self._sample_key = jax.random.PRNGKey(sample_seed)
        self.horizon = int(horizon)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.spec_ngram = int(spec_ngram)
        self.spec_hist = int(spec_hist)
        self._spec_n = 2                    # n-gram match length (bigram)
        self.jit_cache = int(jit_cache)
        if self.jit_cache < 1:
            raise ValueError(f"jit_cache must be >= 1, got {self.jit_cache}")

        if n_blocks is None:
            n_blocks = slots * (max_len // block_size)
        self.paged = paged and any(pageable_block(b) for b in cfg.blocks)

        # ring buffers get `chunk` rows of headroom so chunked prefill is
        # exact for sliding-window attention (steps.init_serving_caches);
        # paged-capable segments get the physical block pool instead of a
        # dense live cache — their device KV bytes are n_blocks·block_size
        # rows, not slots·max_len.
        self.caches = init_serving_caches(
            cfg, slots, max_len, window_headroom=self.chunk,
            round_to=block_size, block_size=block_size,
            n_blocks=n_blocks if self.paged else 0)
        self._prefill = jax.jit(make_slot_prefill_step(
            cfg, max_len, window_headroom=self.chunk, round_to=block_size,
            block_size=block_size, paged=self.paged))
        self._decode = jax.jit(
            make_serving_decode_step(cfg, top_k=self.top_k,
                                     sample=self.temperature > 0),
            donate_argnums=(1,))
        # fused decode executables, one per granted (power-of-two h, spec K)
        # pair — built lazily, bounded LRU (horizon × spec grant combinations
        # must not grow the jit cache without bound)
        self._fused: "OrderedDict[Tuple[int, int], Callable]" = OrderedDict()

        if self.spec_ngram:
            if not speculable(cfg):
                raise ValueError(
                    "spec_ngram needs a single-codebook model whose decode "
                    "state is entirely position-addressed (no SSM/xLSTM "
                    "recurrent segments) — rollback of rejected draft rows "
                    "is a length decrement, which recurrent state and "
                    "codebook frames cannot honor")
            if self.temperature > 0:
                raise ValueError(
                    "spec_ngram is greedy-only (the accept rule compares "
                    "argmaxes); set temperature=0")
            if self.spec_hist < self.spec_ngram + self._spec_n + 1:
                raise ValueError(
                    f"spec_hist {self.spec_hist} too short for K="
                    f"{self.spec_ngram} drafts with {self._spec_n}-gram match")
            if any(b.attn is not None and b.attn.window
                   for b in cfg.blocks) and self.chunk <= self.spec_ngram:
                raise ValueError(
                    "sliding-window ring headroom (prefill_chunk = "
                    f"{self.chunk}) must exceed spec_ngram {self.spec_ngram}: "
                    "a verify tile may overwrite ring rows up to K past the "
                    "committed length")

        # ---- PCRAM reliability layer --------------------------------------
        # True → defaults (wear-leveled allocation, no budget, no scrub);
        # ReliabilityConfig → as given; None/False → off.  The wear
        # *accounting* in the pool is always on (pure host bookkeeping) so
        # the bench can compare allocator policies; budget-driven retirement
        # and the drift scrubber only run with a config attached.
        if reliability is None or reliability is False:
            self.reliability: Optional[ReliabilityConfig] = None
        elif reliability is True:
            self.reliability = ReliabilityConfig()
        else:
            self.reliability = reliability
        rel = self.reliability
        # blocks flagged bad (stuck-at faults, failed retirements) awaiting
        # drain+retire by the sweep — processed even with reliability off so
        # an injected stuck_at fault is always contained
        self._pending_bad: List[int] = []
        self._gauge_tick = 0
        self.pool = BlockPool(
            n_blocks, block_size,
            policy=("min_wear" if rel is not None and rel.wear_leveling
                    else "lifo"),
            endurance_budget=rel.endurance_budget if rel is not None else None)
        # prefix sharing needs the block pool to BE the whole model state:
        # every cache leaf either lives in the pool or is the per-slot `pos`
        # counter the tail prefill re-derives.  Any dense KV row or recurrent
        # state would be skipped by a shared-prefix (tail-only) prefill.
        fully_paged = self.paged and all(
            _leaf_name(p) in POOL_LEAVES + ("pos",)
            for p, _ in jax.tree_util.tree_flatten_with_path(self.caches)[0])
        if prefix_sharing is None:
            prefix_sharing = fully_paged
        elif prefix_sharing and not fully_paged:
            raise ValueError(
                "prefix_sharing=True needs a fully paged cache layout "
                "(non-windowed GQA families with paged=True); this model "
                "keeps per-slot dense/recurrent state a shared block cannot "
                "cover")
        self.prefix_sharing = bool(prefix_sharing)
        # mixed dispatch shares prefix sharing's gate: the fused tile writes
        # prompt KV through the block tables, so every cache leaf must be the
        # pool (or the `pos` counter the mixed step re-derives).  Dense ring
        # or recurrent state would need per-slot multi-row advances the
        # flat mixed rows cannot express for heterogeneous row counts.
        if mixed is None:
            mixed = fully_paged
        elif mixed and not fully_paged:
            raise ValueError(
                "mixed=True needs a fully paged cache layout (non-windowed "
                "GQA families with paged=True); this model keeps per-slot "
                "dense/recurrent state a mixed prefill+decode tile cannot "
                "advance by heterogeneous per-slot row counts")
        self.mixed = bool(mixed)
        self.mixed_budget = int(mixed_budget if mixed_budget is not None
                                else self.chunk + slots)
        if self.mixed and self.mixed_budget < 2:
            raise ValueError(
                f"mixed_budget must be >= 2 (one decode row plus one prefill "
                f"row), got {self.mixed_budget}")
        self.lanes = max(1, (self.mixed_budget - slots) // self.chunk)
        self._mixed: Optional[Callable] = None      # lazily jitted
        prefix_cache = (PrefixCache(self.pool, block_size)
                        if self.prefix_sharing else None)
        self.store = (PagedKVStore(self.caches, swap_blocks, block_size)
                      if swap_blocks else None)
        self.sched = Scheduler(slots, self.pool, max_len,
                               swap_pool=self.store.pool if self.store else None,
                               prefix_cache=prefix_cache,
                               write_span=self.spec_ngram + 1)
        # under mixed dispatch a prompt chain is registered only once its
        # staged replay finishes (Scheduler.finish_prefill) — registering at
        # admission would let a later arrival share blocks whose rows the
        # staged prefill has not written yet
        self.sched.defer_prefix_register = self.mixed
        self.stats = EngineStats()
        self.stats.kv_cache_bytes = self._kv_bytes()
        # MoE counters: per row a dispatch carries, the assignments routed
        # (top_k per MoE layer) and the expert rows computed (every held
        # expert runs drop-free capacity, one row per dispatched row)
        moe = [b for b in cfg.blocks if b.kind == "moe"]
        self._moe_routed = sum(b.n_layers * b.moe.top_k for b in moe)
        self._moe_rows = sum(b.n_layers * b.moe.held for b in moe)
        self.cost_model = OdinCostModel(attribution_cfg or cfg)
        # observability: structured tracer (no-op by default — every emit
        # site is guarded on tracer.enabled so trace-off costs nothing) and
        # the always-on windowed metrics registry
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.tracer.attach(self._now, stats=self.stats,
                           annotate=bool(xla_annotations))
        self._phase_spent = 0.0         # Σ seconds of closed host phases
        self.sched.tracer = self.tracer
        self.pool.tracer = self.tracer
        if self.store is not None:
            self.store.pool.tracer = self.tracer
        self.metrics = MetricsRegistry(window_s=metrics_window)
        # open the first window at t≈0 so no counter movement predates the
        # baseline (maybe_roll's first call only initializes)
        self.metrics.maybe_roll(self._now(), self._counter_snapshot())

        # ---- robustness substrate ----------------------------------------
        self.deadline_s = deadline_s
        self.queue_timeout_s = queue_timeout_s
        self.fault_plan = fault_plan
        self._nan_guard = (bool(nan_guard) if nan_guard is not None
                           else fault_plan is not None)
        self._guarded: Optional[Callable] = None    # lazily jitted
        if degrade is None or degrade is False:
            self.degrade = None
        elif degrade is True:
            self.degrade = DegradationController(tracer=self.tracer)
        elif isinstance(degrade, DegradeConfig):
            self.degrade = DegradationController(degrade, tracer=self.tracer)
        else:
            self.degrade = degrade
        # shutdown latch: drain() (or the front door's SIGTERM handler) sets
        # it, after which late submits get a typed ShuttingDown rejection
        # instead of queueing behind a loop that will never admit them
        self.draining = False
        # only requests carrying lifecycle fields are scanned per step, so
        # a workload without deadlines/cancellations pays nothing here
        self._watched: List[Request] = []
        self._by_rid: Dict[int, Request] = {}
        # observe() deltas for the degradation controller
        self._churn_mark = 0
        self._spec_mark = (0, 0)

        K = cfg.n_codebooks
        tok_shape = (slots, K, 1) if K > 1 else (slots, 1)
        self._last_tok = jnp.zeros(tok_shape, jnp.int32)
        # per-slot token-history ring for the on-device n-gram draft match
        # (right-aligned, -1 padded; shifted on-device inside the spec scan)
        self._hist = (jnp.full((slots, self.spec_hist), -1, jnp.int32)
                      if self.spec_ngram else None)
        self._slot_len = np.zeros(slots, np.int32)
        self._tables = np.zeros((slots, self.n_pages), np.int32)
        self._tables_dev = jnp.asarray(self._tables)
        self._synced_version = self.sched.table_version
        self._done: List[Request] = []

    # ------------------------------------------------------------------ util

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = self._clock()
        t = self._clock() - self._t0 + self._skew
        if t < self._last_now:          # monotone clamp (clock-skew faults)
            t = self._last_now
        else:
            self._last_now = t
        return t

    def _kv_bytes(self) -> int:
        """Device bytes held by KV-bearing cache leaves (the paged-vs-dense
        memory observable the serving benchmark records)."""
        names = SEQ_LEAVES + POOL_LEAVES
        return int(sum(
            l.nbytes for p, l in jax.tree_util.tree_flatten_with_path(self.caches)[0]
            if _leaf_name(p) in names))

    @staticmethod
    def _slot_track(slot: int) -> str:
        return f"slot {slot}"

    def _phase(self, name: str) -> _HostPhase:
        """One host phase: ``plan``, ``pack``, ``tables``, a dispatch's kind
        (its launch), ``sync``, ``wear``, ``emit``; the front door adds
        ``deliver`` and ``idle``."""
        return _HostPhase(self, name)

    def _counter_snapshot(self) -> Dict[str, float]:
        """Cumulative counters the metrics registry turns into window deltas."""
        st = self.stats
        return {"generated_tokens": st.generated_tokens,
                "decode_tokens": st.decode_tokens,
                "prefill_tokens": st.prefill_tokens,
                "dispatches": st.dispatches,
                "decode_dispatches": st.decode_dispatches,
                "mixed_dispatches": st.mixed_dispatches,
                "host_syncs": st.host_syncs,
                "preempt_swap": st.preempt_swap,
                "preempt_recompute": st.preempt_recompute,
                "spec_drafted": st.spec_drafted,
                "spec_accepted": st.spec_accepted,
                "spec_overhead_rows": st.spec_overhead_rows,
                "decode_time_s": st.decode_time,
                "prefill_time_s": st.prefill_time,
                "pool_writes": st.pool_writes,
                "retired_blocks": st.retired_blocks,
                "scrub_copies": st.scrub_copies,
                "scrub_rows": st.scrub_rows,
                "moe_routed": st.moe_routed,
                "moe_held": st.moe_held,
                "moe_rows": st.moe_rows}

    def _count_moe(self, held, routed_rows: int, rows: int) -> None:
        """MoE counters of one dispatch: ``held`` the step's ``moe_held``
        outputs (host copies; none for a dense model), ``routed_rows`` the
        rows that carried a token, ``rows`` the rows the program ran."""
        if not held:
            return
        st = self.stats
        n = int(sum(int(np.sum(h)) for h in held))
        st.moe_held += n
        st.moe_routed += routed_rows * self._moe_routed
        st.moe_rows += rows * self._moe_rows
        if self.tracer.enabled:
            self.tracer.counter("moe held", "moe",
                                {"held": n,
                                 "routed": routed_rows * self._moe_routed,
                                 "rows": rows * self._moe_rows})

    def _set_last_tok(self, slot: int, tok) -> None:
        tok = jnp.asarray(tok, jnp.int32).reshape(self._last_tok.shape[1:])
        self._last_tok = self._last_tok.at[slot].set(tok)

    def _seed_hist(self, req: Request) -> None:
        """(Re)build the slot's draft-match history from the request's full
        token context (prompt + every generated token, pending included) —
        host-side only at admission/resume; the spec scan shifts emitted
        tokens in on-device."""
        ctx = np.concatenate([np.asarray(req.replay_tokens(), np.int32).ravel(),
                              np.ravel(req.generated[-1]).astype(np.int32)])
        row = np.full(self.spec_hist, -1, np.int32)
        tail = ctx[-self.spec_hist:]
        row[self.spec_hist - len(tail):] = tail
        self._hist = self._hist.at[req.slot].set(jnp.asarray(row))

    def _refresh_tables(self) -> jax.Array:
        """Device mirror of running requests' block tables ([slots, P] int32).

        Dirty-tracked against ``Scheduler.table_version``: the host loop and
        the host→device upload only run on steps where some table actually
        changed (growth, admission, preemption, resume, completion, horizon
        pre-extension) — steady-state decode reuses the cached device array.
        Entries past a table's length are stale ids — harmless, the kernel
        masks pages at or beyond the slot's length."""
        if self._synced_version != self.sched.table_version:
            for slot, req in self.sched.running.items():
                bt = req.block_table
                self._tables[slot, :len(bt)] = bt
            self._tables_dev = jnp.asarray(self._tables)
            self._synced_version = self.sched.table_version
        return self._tables_dev

    def _first_token(self, last_logits, req: Request) -> np.ndarray:
        """The request's first generated token from its prefill logits:
        greedy, or the engine's temperature/top-k sampling with a per-request
        key (host-side — prefill logits are already on the host path)."""
        logits = np.asarray(last_logits, np.float32)[0]        # [V] or [K, V]
        if self.temperature <= 0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        if self.top_k:
            kth = np.sort(logits, axis=-1)[..., -self.top_k, None]
            logits = np.where(logits >= kth, logits, -np.inf)
        rng = np.random.default_rng((self.sample_seed, req.rid))
        z = logits / max(self.temperature, 1e-6) + rng.gumbel(size=logits.shape)
        z = np.where(np.isfinite(logits), z, -np.inf)
        return np.argmax(z, axis=-1).astype(np.int32)

    def _emit(self, req: Request, tok: np.ndarray, now: float) -> None:
        req.generated.append(tok)
        self.stats.generated_tokens += 1
        if self.eos_id is not None and int(np.ravel(tok)[0]) == self.eos_id:
            req.eos = True                 # first codebook, same as on-device
        if req.t_first_token is None:
            req.t_first_token = now
            ttft = max(0.0, now - req.arrival)
            self.metrics.observe("ttft_s", ttft)
            if req.tenant is not None:
                self.metrics.observe(f"ttft_s/{req.tenant}", ttft)
        if self.on_token is not None:
            self.on_token(req, tok, now)

    # ------------------------------------------------------------- lifecycle

    @staticmethod
    def _extras_worst_replay(req: Request) -> int:
        """Worst-case rows a (re-)prefill of this request can ever replay:
        the prompt plus every generated token except the pending one (a
        recompute preemption at max_new-1 generated tokens replays exactly
        this many)."""
        return req.prompt_len + req.max_new - 1

    def _check_extras_fit(self, req: Request) -> None:
        """THE extras/chunk guard — shared by submit() and the prefill path
        so the two can never disagree.  The extras overlay (patch_embeds /
        pos3d) only works when the whole replay lands in a single prefill
        chunk; checking the worst-case replay length here means a request
        that passes submit() can never be rejected mid-run at readmission."""
        worst = self._extras_worst_replay(req)
        if req.extras and worst > self.chunk:
            raise ValueError(
                f"request {req.rid}: extras (patch_embeds/pos3d) need the "
                f"worst-case replay (prompt+max_new-1 = {worst}) to fit one "
                f"prefill chunk ({self.chunk})")

    def submit(self, req: Request) -> None:
        if self.draining:
            raise ShuttingDown(
                f"request {req.rid}: engine is draining — submissions after "
                f"drain() begin get a typed rejection, never a silent hang")
        self._check_extras_fit(req)
        if req.deadline is None and self.deadline_s is not None:
            req.deadline = req.arrival + self.deadline_s
        if req.queue_timeout is None and self.queue_timeout_s is not None:
            req.queue_timeout = self.queue_timeout_s
        self.sched.submit(req)
        self._by_rid[req.rid] = req
        if (req.deadline is not None or req.queue_timeout is not None
                or req.cancel_at is not None):
            self._watched.append(req)
        if self.tracer.enabled:
            t = self._now()
            # the flow "s" anchor: every later lifecycle event for this rid
            # hangs off this arrow chain (admit → prefill → … → complete)
            self.tracer.flow_event("s", "request", "scheduler", req.rid, ts=t)
            args = {"rid": req.rid, "prompt_tokens": req.prompt_len,
                    "max_new": req.max_new}
            if req.tenant is not None:
                args["tenant"] = req.tenant
            self.tracer.instant("queued", "lifecycle", "scheduler", ts=t,
                                args=args, flow=req.rid)

    def _complete(self, req: Request, now: float) -> None:
        slot = req.slot
        self.sched.complete(req, now)
        self._done.append(req)
        if req.t_first_token is not None and req.n_generated > 1:
            tpot = max(0.0, (now - req.t_first_token) / (req.n_generated - 1))
            self.metrics.observe("tpot_s", tpot)
            if req.tenant is not None:
                self.metrics.observe(f"tpot_s/{req.tenant}", tpot)
        if self.tracer.enabled:
            track = self._slot_track(slot) if slot >= 0 else "scheduler"
            args = {"rid": req.rid, "generated_tokens": req.n_generated,
                    "eos": bool(req.eos)}
            if req.tenant is not None:
                args["tenant"] = req.tenant
            self.tracer.instant("complete", "lifecycle", track, ts=now,
                                args=args, flow=req.rid)
            self.tracer.flow_event("f", "request", track, req.rid, ts=now)

    _TERMINAL_EVENT = {RequestState.TIMEOUT: "timeout",
                       RequestState.CANCELLED: "cancel",
                       RequestState.FAILED: "failed"}

    def _finalize(self, req: Request, state: RequestState, reason: str,
                  now: float) -> None:
        """Release a live request into a non-DONE terminal state (the DONE
        path stays :meth:`_complete`): scheduler teardown from wherever it
        is in the lifecycle, terminal bookkeeping, lifecycle trace events."""
        slot = req.slot
        self.sched.release(req, state, now, reason)
        self._done.append(req)
        if state is RequestState.TIMEOUT:
            self.stats.timeouts += 1
        elif state is RequestState.CANCELLED:
            self.stats.cancelled += 1
        else:
            self.stats.failed += 1
        if self.tracer.enabled:
            track = self._slot_track(slot) if slot >= 0 else "scheduler"
            args = {"rid": req.rid, "reason": reason,
                    "generated_tokens": req.n_generated}
            if req.tenant is not None:
                args["tenant"] = req.tenant
            self.tracer.instant(
                self._TERMINAL_EVENT[state], "lifecycle", track, ts=now,
                args=args, flow=req.rid)
            self.tracer.flow_event("f", "request", track, req.rid, ts=now)

    def cancel(self, rid: int, reason: str = "client") -> bool:
        """Client-side cancellation: release request ``rid`` from any live
        state (slot freed, refcount claims dropped, swap ticket returned).
        Returns False when the rid is unknown or already terminal — cancel
        is idempotent and never raises."""
        req = self._by_rid.get(rid)
        if req is None or req.terminal:
            return False
        self._finalize(req, RequestState.CANCELLED, reason, self._now())
        return True

    def _expire(self, now: float) -> None:
        """Sweep watched requests for scripted cancellations, deadlines and
        queue timeouts.  Runs at the top of each step, so a mid-horizon
        deadline is enforced at the next step boundary (grant_horizon's
        deadline cap keeps that boundary close)."""
        alive: List[Request] = []
        for req in self._watched:
            if req.terminal:
                continue
            if req.cancel_at is not None and now >= req.cancel_at:
                self._finalize(req, RequestState.CANCELLED, "client", now)
            elif req.deadline is not None and now >= req.deadline:
                self._finalize(req, RequestState.TIMEOUT, "deadline", now)
            elif (req.queue_timeout is not None and req.t_admit is None
                    and now >= req.arrival + req.queue_timeout):
                self._finalize(req, RequestState.TIMEOUT, "queue", now)
            else:
                alive.append(req)
        self._watched = alive

    def _apply_faults(self, now: float):
        """Consume this step's fault events from the plan.  Arming faults
        (alloc/swap/clock) mutate the seams directly; a ``nan_logits`` event
        is returned for the decode phase to inject through the guarded
        executable."""
        nan_ev = None
        for ev in self.fault_plan.events_at(self.stats.steps):
            self.stats.faults_injected += 1
            if ev.site == "alloc":
                self.pool.arm_alloc_failures(ev.count)
                self.stats.alloc_faults += ev.count
                self.fault_plan.record(ev, "armed", count=ev.count)
            elif ev.site in ("swap_out", "swap_in"):
                if self.store is None:
                    self.fault_plan.record(ev, "skipped-no-swap-tier")
                else:
                    self.store.arm_swap_failures(ev.site[5:], ev.count)
                    self.fault_plan.record(ev, "armed", count=ev.count)
            elif ev.site == "clock_skew":
                self._skew += ev.skew_s
                self.fault_plan.record(ev, "applied", skew_s=ev.skew_s)
            elif ev.site == "stuck_at":
                # one PCRAM block develops a stuck-at cell: flag it for the
                # reliability sweep to drain+retire before the next dispatch
                if self.pool.n_blocks == 0:
                    self.fault_plan.record(ev, "skipped-empty-pool")
                else:
                    bid = ev.slot % self.pool.n_blocks
                    if bid in self.pool.retired:
                        self.fault_plan.record(ev, "already-retired", block=bid)
                    else:
                        self._pending_bad.append(bid)
                        self.fault_plan.record(ev, "flagged", block=bid)
            elif ev.site == "wear_exhaustion":
                # the count most-worn live blocks burn through their
                # remaining endurance at once — a retirement storm
                order = np.argsort(self.pool.wear, kind="stable")[::-1]
                picked = [int(b) for b in order
                          if int(b) not in self.pool.retired][:ev.count]
                self._pending_bad.extend(picked)
                self.fault_plan.record(ev, "flagged", blocks=picked)
            elif ev.site == "nan_logits":
                if self._nan_guard:
                    nan_ev = ev
                else:
                    self.fault_plan.record(ev, "skipped-guard-off")
            if self.tracer.enabled:
                self.tracer.instant("fault-inject", "faults", "scheduler",
                                    ts=now, args={"site": ev.site,
                                                  "step": ev.step,
                                                  "count": ev.count})
        return nan_ev

    def _observe_degrade(self, now: float) -> None:
        """Feed the controller this step's observables and push its knobs
        into the scheduler (admission hold, prefix retention) — decode-side
        knobs (spec K, horizon cap) are read in the decode routing."""
        ctl = self.degrade
        churn_now = self.stats.preempt_swap + self.stats.preempt_recompute
        churn = churn_now - self._churn_mark
        self._churn_mark = churn_now
        d_draft = self.stats.spec_drafted - self._spec_mark[0]
        d_acc = self.stats.spec_accepted - self._spec_mark[1]
        self._spec_mark = (self.stats.spec_drafted, self.stats.spec_accepted)
        ctl.observe(
            now,
            # occupancy over the SURVIVING capacity: retirement shrinks the
            # denominator, so sustained bad-block loss reads as pressure
            # through the same pool_frac trigger load always has
            pool_frac=self.pool.used_blocks / max(1, self.pool.usable_blocks),
            queue_depth=sum(1 for a, _, _ in self.sched.waiting if a <= now),
            churn=churn,
            accept_rate=(d_acc / d_draft) if d_draft else None,
            est_step_time=self._est_step_time(),
            active=len(self.sched.running),
            retired_frac=len(self.pool.retired) / max(1, self.pool.n_blocks))
        self.sched.admission_hold = (ctl.retry_after(now)
                                     if ctl.deny_admission else None)
        self.sched.prefix_retain = not ctl.release_prefix
        cache = self.sched.prefix_cache
        if ctl.release_prefix and cache is not None:
            n = cache.reclaimable()
            if n:
                cache.reclaim(n)
        self.stats.degrade_level = ctl.level
        self.stats.degrade_transitions = ctl.transitions

    def drain(self, max_steps: int = 100_000) -> Dict:
        """Graceful shutdown: cancel every request that never started
        (reason "drain"), then drive the loop until all in-flight work —
        running, swapped, and preempted-but-admitted requests — finishes.
        Once draining, late :meth:`submit` calls raise :class:`ShuttingDown`.
        Returns the final summary."""
        self.draining = True
        now = self._now()
        for _, _, req in list(self.sched.waiting):
            if req.t_admit is None:
                self._finalize(req, RequestState.CANCELLED, "drain", now)
        steps = 0
        while self.sched.has_work:
            self.step()
            steps += 1
            if steps > max_steps:
                raise EngineStallError(
                    f"drain exceeded {max_steps} steps",
                    summary=self.summary())
        return self.summary()

    def _cow_fork(self, src: int, dst: int) -> None:
        """Execute a COW fork: copy pool block ``src`` into ``dst`` on every
        pool leaf, before the forking slot writes its tail rows into ``dst``."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.caches)
        t0 = self._now() if self.tracer.enabled else 0.0
        out = []
        for path, leaf in flat:
            if _leaf_name(path) in POOL_LEAVES:
                leaf = leaf.at[:, dst].set(leaf[:, src])
            out.append(leaf)
        self.caches = jax.tree_util.tree_unflatten(treedef, out)
        self.stats.cow_forks += 1
        # endurance: the fork physically programs a full block at dst
        self.pool.record_writes([(dst, self.block_size)], self._now())
        self.stats.pool_writes = self.pool.total_writes
        if self.tracer.enabled:
            self.tracer.span("cow-copy", "dispatch", "pool", t0,
                             self._now() - t0,
                             args={"kind": "cow-copy", "src": src, "dst": dst})

    # ------------------------------------------------- PCRAM reliability

    def _record_writes(self, req: Request, start: int, rows: int,
                       now: float) -> None:
        """Host-side endurance mirror of one dispatch's KV writes: bill rows
        ``[start, start+rows)`` of the request's sequence to the pool blocks
        its table maps them to.  Rows past the table's span are parked on
        the kernel's write-off block (never a real pool block) — skipped."""
        if rows <= 0:
            return
        bs = self.block_size
        table = req.block_table
        pairs = []
        row = start
        end = min(start + rows, self.max_len)
        while row < end:
            bi = row // bs
            if bi >= len(table):
                break                       # write-off parking, not PCRAM
            n = min(end, (bi + 1) * bs) - row
            pairs.append((table[bi], n))
            row += n
        if pairs:
            self.pool.record_writes(pairs, now)
            self.stats.pool_writes = self.pool.total_writes

    def _update_wear_gauges(self) -> None:
        if self.pool.n_blocks:
            self.stats.wear_p99 = float(np.percentile(self.pool.wear, 99))
            self.stats.wear_max = int(self.pool.wear.max())

    def _maybe_update_wear_gauges(self) -> None:
        """Per-step gauge refresh, throttled: wear moves by at most one
        block's worth of rows per dispatch, but the percentile scan costs
        more than the rest of the sweep — every 16th sweep tracks it
        closely enough, and ``summary()`` recomputes exactly at read time."""
        self._gauge_tick = (self._gauge_tick + 1) % 16
        if self._gauge_tick == 0:
            self._update_wear_gauges()

    def _block_rewrite(self, pairs: List[Tuple[int, int]], kind: str,
                       now: float) -> None:
        """Execute block copies on the physical store and bill them: each
        ``(src, dst)`` pair copies identical bytes (``src == dst`` for a
        drift refresh in place), costs one block of PCRAM writes at the
        destination, and is traced as a ``scrub`` span carrying its ODIN
        energy — the rows land in the ``scrub`` phase of ``odin_phases``,
        so span energies still sum exactly to ``odin_total``."""
        if not pairs:
            return
        t0 = self._now()
        # identity pairs (drift refresh in place) are byte no-ops on the
        # functional cache arrays — executing them would copy whole pool
        # leaves per sweep, an O(pool) simulation artifact with no modeled
        # counterpart.  The physical PCRAM rewrite they represent is billed
        # below (wear, energy, trace) exactly as if the scatter had run.
        moves = [(s, d) for s, d in pairs if s != d]
        if self.paged and moves:
            src = jnp.asarray([s for s, _ in moves], jnp.int32)
            dst = jnp.asarray([d for _, d in moves], jnp.int32)
            flat, treedef = jax.tree_util.tree_flatten_with_path(self.caches)
            out = []
            for path, leaf in flat:
                if _leaf_name(path) in POOL_LEAVES:
                    leaf = leaf.at[:, dst].set(leaf[:, src])
                out.append(leaf)
            self.caches = jax.tree_util.tree_unflatten(treedef, out)
        rows = len(pairs) * self.block_size
        self.pool.record_writes([(d, self.block_size) for _, d in pairs], now)
        self.stats.pool_writes = self.pool.total_writes
        self.stats.scrub_copies += len(pairs)
        self.stats.scrub_rows += rows
        if self.tracer.enabled:
            self.tracer.span(
                "scrub", "dispatch", "pool", t0, self._now() - t0,
                args={"kind": kind, "blocks": len(pairs), "rows": rows,
                      "odin_energy_mj": self.cost_model.energy_mj(rows)})

    def _reliability_sweep(self, now: float) -> None:
        """Bad-block retirement + drift-refresh scrubbing, run between the
        fault sweep and ``plan()`` so no dispatch is in flight while block
        ids move.  Retirement drains each bad block through a block copy,
        remaps every live claim (tables, kept prefixes, prefix cache) and
        shrinks the usable pool; requests the surviving capacity can never
        hold again are failed typed (``capacity``) instead of livelocking
        admission.  Copies move identical bytes, so greedy streams stay
        bit-identical with reliability on vs. off."""
        rel = self.reliability
        bad = list(self._pending_bad)
        if rel is not None and rel.endurance_budget is not None:
            bad.extend(self.pool.over_budget())
        if bad:
            bad = sorted(set(bad))
            copies = self.sched.retire_blocks(bad)
            self._pending_bad = [b for b in bad if b not in self.pool.retired]
            self._block_rewrite(copies, "retire-drain", now)
            self.stats.retired_blocks = len(self.pool.retired)
            if self.tracer.enabled and copies:
                self.tracer.counter(
                    "retired blocks", "pool",
                    {"retired": len(self.pool.retired),
                     "usable": self.pool.usable_blocks})
            # capacity containment: a request whose full footprint no longer
            # fits the surviving pool can never finish — one typed terminal
            # state now beats an admission livelock forever
            usable = self.pool.usable_blocks
            for req in self._all_live():
                if self.pool.blocks_for(req.prompt_len + req.max_new) > usable:
                    self._finalize(req, RequestState.FAILED, "capacity", now)
        if rel is not None and rel.scrub_enabled:
            self._scrub(now, rel)
        self._maybe_update_wear_gauges()

    def _scrub(self, now: float, rel: ReliabilityConfig) -> None:
        """Drift refresh: rewrite the oldest-written resident blocks in
        place (identical bytes — PCRAM re-SET/RESET restores the analog
        level before drift crosses the read margin), at most ``scrub_rate``
        blocks per step, once their last write is older than the drift
        deadline."""
        lw = self.pool.last_write
        cand = np.flatnonzero((lw >= 0) & (now - lw >= rel.drift_deadline_s))
        due = [int(b) for b in cand
               if self.pool.refs(int(b)) > 0 and int(b) not in self.pool.retired]
        if not due:
            return
        due.sort(key=lambda b: lw[b])
        batch = due[:rel.scrub_rate]
        self._block_rewrite([(b, b) for b in batch], "drift-refresh", now)

    def _all_live(self) -> List[Request]:
        live = [r for _, _, r in self.sched.waiting]
        live += list(self.sched.swapped)
        live += list(self.sched.running.values())
        return [r for r in live if not r.terminal]

    def _prefill_request(self, req: Request, now: float,
                         grant: Optional[PrefixGrant] = None) -> None:
        """Chunked prefill into the request's slot; emits the first token for
        fresh admissions (readmitted requests already hold their pending
        token — re-prefill only rebuilds the KV they lost).  A shared-prefix
        ``grant`` skips the resident rows: after the COW fork copy (if any),
        only ``[grant.start:]`` of the replay tokens run through the model —
        their queries read the shared prefix through the slot's block table.
        """
        fresh = req.n_generated == 0
        toks = req.replay_tokens()
        ntok = toks.shape[-1]
        extras = req.extras or {}
        if extras:
            self._check_extras_fit(req)     # same bound submit() enforced
        pos3d = extras.get("pos3d") if extras else None
        if pos3d is not None:
            pos3d = np.asarray(pos3d)
            if ntok > pos3d.shape[0]:
                # recompute replay covers generated tokens too: extend with
                # the degenerate (t, t, t) text positions decode would use
                tail = np.repeat(np.arange(pos3d.shape[0], ntok,
                                           dtype=pos3d.dtype)[:, None], 3, axis=1)
                pos3d = np.concatenate([pos3d, tail], axis=0)
        start0 = 0
        if grant is not None:
            if grant.fork is not None:
                self._cow_fork(*grant.fork)
            start0 = grant.start
            self.stats.prefix_hit_tokens += start0
            self.stats.shared_prefix_blocks += grant.shared_blocks
        trace = self.tracer.enabled
        # one clock domain for everything this dispatch records: metrics
        # walls, stats time accounting and trace spans all read the engine
        # clock (injectable / skew-clamped), never time.perf_counter —
        # a deterministic test clock must see them agree exactly
        t0 = self._now()
        chunk_sizes: List[int] = []
        # prefill writes K/V blocks straight into the pool via this row
        # (admission bumped table_version, so the mirror refreshes here)
        with self._phase("tables"):
            tables = self._refresh_tables()
        start = start0
        ll = None
        held = []
        with self._phase("prefill"):
            while start < ntok:
                c = min(self.chunk, ntok - start)
                chunk_toks = jnp.asarray(toks[..., start:start + c][None])
                kw = {}
                if extras:
                    if extras.get("patch_embeds") is not None:
                        kw["patch_embeds"] = jnp.asarray(extras["patch_embeds"])[None]
                    if pos3d is not None:
                        kw["pos3d"] = jnp.asarray(pos3d)[None][:, start:start + c]
                ll, self.caches, *h = self._prefill(
                    self.params, self.caches, chunk_toks,
                    jnp.int32(req.slot), jnp.int32(start), jnp.bool_(start == start0),
                    tables, **kw)
                held += h
                self.stats.dispatches += 1
                chunk_sizes.append(c)
                start += c
        with self._phase("sync"):
            jax.block_until_ready(ll)
            held = jax.device_get(held)
            if fresh:
                ll = np.asarray(ll)          # the first token's logits
        wall = self._now() - t0
        with self._phase("wear"):
            # endurance mirror: the replay scattered rows [start0, ntok) into
            # the request's blocks (shared prefix rows were read, not written)
            self._record_writes(req, start0, ntok - start0, self._now())
        with self._phase("emit"):
            self.stats.host_syncs += 1
            self.stats.prefill_time += wall
            self.stats.prefill_tokens += ntok - start0
            self._count_moe(held, ntok - start0, ntok - start0)
            req.n_prefill_tokens += ntok - start0
            self.metrics.observe("dispatch_prefill_s", wall)
            if trace:
                # chunks are not individually synced, so the dispatch's
                # engine-clock span is split across chunks proportionally to
                # their rows (as horizon token timestamps are interpolated)
                span = wall
                track = self._slot_track(req.slot)
                total = max(1, ntok - start0)
                self.tracer.flow_event("t", "request", track, req.rid, ts=t0)
                off, pos = t0, start0
                for i, c in enumerate(chunk_sizes):
                    dur = span * c / total
                    self.tracer.span(
                        "prefill-chunk", "dispatch", track, off, dur,
                        args={"kind": "prefill-chunk", "rid": req.rid,
                              "slot": req.slot, "start": pos, "rows": c,
                              "prefix_hit_tokens": start0 if i == 0 else 0,
                              "host_syncs": int(i == len(chunk_sizes) - 1),
                              "interpolated": len(chunk_sizes) > 1,
                              "odin_energy_mj": self.cost_model.energy_mj(c)},
                        flow=req.rid)
                    off += dur
                    pos += c
            self._slot_len[req.slot] = ntok
            if fresh:
                tok = self._first_token(ll, req)                   # [] or [K]
                self._emit(req, tok, self._now())
                pending = tok
            else:
                pending = req.generated[-1]
            self._set_last_tok(req.slot, pending)
            if self.spec_ngram:
                self._seed_hist(req)

    # -------------------------------------------------- mixed dispatch path

    def _reset_slot_pos(self, slot: int, value: int) -> None:
        """Set every cache ``pos`` leaf for ``slot`` (fully paged layouts
        keep no other per-slot state, so this is the whole slot reset a
        staged admission needs before its first mixed dispatch)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.caches)
        out = []
        for path, leaf in flat:
            if _leaf_name(path) == "pos":
                leaf = leaf.at[..., slot].set(jnp.int32(value))
            out.append(leaf)
        self.caches = jax.tree_util.tree_unflatten(treedef, out)

    def _stage_mixed_admission(self, req: Request,
                               grant: Optional[PrefixGrant] = None) -> None:
        """Admission under mixed dispatch: run the COW fork and shared-prefix
        accounting now, then mark the request mid-prefill — its replay is
        staged through fused mixed dispatches (``_dispatch_mixed``), chunk
        rows at a time, instead of the separate prefill loop."""
        start0 = 0
        if grant is not None:
            if grant.fork is not None:
                self._cow_fork(*grant.fork)
            start0 = grant.start
            self.stats.prefix_hit_tokens += start0
            self.stats.shared_prefix_blocks += grant.shared_blocks
        req.prefilling = True
        req.prefill_pos = start0
        self._slot_len[req.slot] = start0
        self._reset_slot_pos(req.slot, start0)
        if self.tracer.enabled:
            self.tracer.flow_event("t", "request",
                                   self._slot_track(req.slot), req.rid)

    def _mixed_fn(self) -> Callable:
        """Lazily-jitted mixed prefill+decode step.  One jit object; XLA
        retraces per lane width Q, and the engine snaps Q to the next power
        of two so the executable count is bounded by log2(chunk)+1."""
        if self._mixed is None:
            self._mixed = jax.jit(
                make_serving_mixed_step(self.cfg, top_k=self.top_k,
                                        sample=self.temperature > 0),
                donate_argnums=(1,))
        return self._mixed

    def _dispatch_mixed(self) -> None:
        """ONE fused dispatch over both populations: decode slots at one row
        each plus up to ``lanes`` mid-prefill slots at ``≤ chunk`` rows each,
        packed by ``Scheduler.pack_mixed`` under the ``mixed_budget`` row
        budget.  The program runs ``slots + lanes·Q`` rows (a decode group
        and the prefill lanes, ``nn.attention.MixedRows``), Q the widest
        part snapped to a power of two.

        Decode rows emit exactly what the single-step path would have
        emitted (the decode group makes the decode program's kernel call);
        a prefill slot whose replay completes here gets its first token from
        ``last_logits`` through the same host-side ``_first_token`` path as
        the separate prefill — greedy mixed-on streams are bit-identical to
        mixed-off."""
        with self._phase("pack"):
            decode, parts = self.sched.pack_mixed(self.mixed_budget,
                                                  self.chunk, self.lanes)
            if not decode and not parts:
                return
            q_max = max([1] + [c for _, _, c in parts])
            Q = 1 << (q_max - 1).bit_length()   # pow-2 lane widths, bounded
            B, L, K = self.slots, self.lanes, self.cfg.n_codebooks
            n_rows = B + L * Q
            dec_tok = np.zeros((B, K) if K > 1 else B, np.int32)
            lane_tok = np.zeros((L, K, Q) if K > 1 else (L, Q), np.int32)
            dm = np.zeros(B, bool)
            lane_slot = np.zeros(L, np.int32)
            lane_lens = np.zeros(L, np.int32)
            for r in decode:
                dm[r.slot] = True
                # the pending token is host-resident in the stream — no
                # device readback of _last_tok needed to build the rows
                dec_tok[r.slot] = np.asarray(r.generated[-1], np.int32)
            for lane, (r, start, c) in enumerate(parts):
                lane_slot[lane], lane_lens[lane] = r.slot, c
                lane_tok[lane, ..., Q - c:] = np.asarray(   # right-aligned
                    r.replay_tokens(), np.int32)[..., start:start + c]
        t0 = self._now()            # engine clock: metrics ≡ stats ≡ trace
        with self._phase("tables"):
            tables = self._refresh_tables()
        with self._phase("mixed"):
            key = jax.random.fold_in(self._sample_key, self.stats.decode_steps)
            nxt, last_logits, self.caches, *held = self._mixed_fn()(
                self.params, self.caches, jnp.asarray(dec_tok),
                jnp.asarray(lane_tok), jnp.asarray(self._slot_len),
                jnp.asarray(dm),
                jnp.asarray(lane_slot), jnp.asarray(lane_lens), tables, key,
                jnp.float32(self.temperature))
        with self._phase("sync"):
            host, held = jax.device_get((nxt, held))  # syncs the step
            ll_host = np.asarray(last_logits) if parts else None
        wall = self._now() - t0
        now = self._now()
        with self._phase("wear"):
            for r in decode:
                self._record_writes(r, int(self._slot_len[r.slot]), 1, now)
            for r, start, c in parts:
                self._record_writes(r, start, c, now)
        with self._phase("emit"):
            dec_rows = len(decode)
            pre_rows = sum(c for _, _, c in parts)
            rows = dec_rows + pre_rows
            # phase-attributed time: the dispatch is one wall, split across
            # the decode/prefill ledgers proportionally to their rows
            self.stats.decode_time += wall * dec_rows / rows
            self.stats.prefill_time += wall * pre_rows / rows
            self.metrics.observe("dispatch_mixed_s", wall)
            self.stats.dispatches += 1
            self.stats.host_syncs += 1
            self.stats.mixed_dispatches += 1
            self.stats.mixed_decode_rows += dec_rows
            self.stats.mixed_prefill_rows += pre_rows
            self.stats.mixed_tile_rows += n_rows
            self.stats.mixed_prefill_deferred += self.sched.lane_deferred
            self._count_moe(held, rows, n_rows)
            if self.tracer.enabled:
                self.tracer.span(
                    "mixed", "dispatch", "dispatch", t0, wall,
                    args={"kind": "mixed", "q_tile": Q, "tile_rows": n_rows,
                          "lanes": L,
                          "slots_active": len(decode) + len(parts),
                          "decode_rows": dec_rows, "prefill_rows": pre_rows,
                          "tokens": dec_rows, "rows": rows, "host_syncs": 1,
                          "odin_energy_mj": self.cost_model.energy_mj(rows)})
            if decode:
                # the decode sampling-key schedule only advances when decode
                # rows actually rode along (pure-prefill dispatches don't
                # burn a fold_in index the separate path never would have)
                self.stats.decode_steps += 1
                self.stats.decode_dispatches += 1
                self.stats.active_slot_steps += dec_rows
                self.stats.slot_steps += self.slots
                dmj = jnp.asarray(dm).reshape(
                    (self.slots,) + (1,) * (self._last_tok.ndim - 1))
                self._last_tok = jnp.where(dmj, nxt, self._last_tok)
                if self.spec_ngram:
                    # speculable ⇒ single codebook, so nxt is [slots, 1]
                    shifted = jnp.concatenate([self._hist[:, 1:], nxt],
                                              axis=1)
                    self._hist = jnp.where(jnp.asarray(dm)[:, None], shifted,
                                           self._hist)
            for r in decode:
                self._slot_len[r.slot] += 1
                self.stats.decode_tokens += 1
                self._emit(r, host[r.slot, ..., 0], now)
                if r.done:
                    self._complete(r, now)
            for r, start, c in parts:
                r.prefill_pos = start + c
                self._slot_len[r.slot] = r.prefill_pos
                self.stats.prefill_tokens += c
                r.n_prefill_tokens += c
                if r.prefill_pos < r.cached_len:
                    continue                        # more chunks to stage
                self.sched.finish_prefill(r)
                if r.n_generated == 0:
                    tok1 = self._first_token(ll_host[r.slot:r.slot + 1], r)
                    self._emit(r, tok1, now)
                    pending = tok1
                else:
                    # readmitted after a recompute preemption: the pending
                    # token survived host-side, the replay only rebuilt the KV
                    pending = r.generated[-1]
                self._set_last_tok(r.slot, pending)
                if self.spec_ngram:
                    self._seed_hist(r)
                if r.done:
                    self._complete(r, now)

    def step(self) -> bool:
        """One engine iteration; returns True while work remains.

        Injected faults are *contained* here: an armed allocation failure
        surfaces as preemption/denial through the planner's normal fallback
        paths, a swap-copy fault downgrades the victim to recompute, a
        NaN-poisoned slot is quarantined by the guarded decode, and clock
        skew is clamped monotone — no fault event ever escapes ``step()``
        as an exception.

        Host phases tile the step: ``plan``, then the chosen dispatch's own
        phases (``pack``, ``tables``, its launch, ``sync``, ``wear``,
        ``emit``), then ``emit`` for the step's closing bookkeeping."""
        with self._phase("plan"):
            dispatch = self._plan_step()
        if dispatch is not None:
            dispatch()
        with self._phase("emit"):
            self.stats.steps += 1
            if self.degrade is not None:
                self._observe_degrade(self._now())
            self.metrics.maybe_roll(self._now(), self._counter_snapshot())
        return self.sched.has_work

    def _plan_step(self) -> Optional[Callable[[], None]]:
        """A step up to its dispatch: expiry, faults, the reliability sweep,
        ``Scheduler.plan``, preemption and resume copies, admissions (a
        separate prefill dispatches here), and the choice of dispatch,
        returned to run."""
        now = self._now()
        if self._watched:
            self._expire(now)
        nan_ev = None
        if self.fault_plan is not None:
            nan_ev = self._apply_faults(now)
            now = self._now()              # clock skew may have moved it
        # PCRAM reliability sweep: retire flagged/over-budget blocks and run
        # the drift scrubber BEFORE planning, so block ids never move under
        # an in-flight dispatch.  Pending stuck-at blocks are processed even
        # with reliability off — fault containment is not optional.
        if self._pending_bad or self.reliability is not None:
            self._reliability_sweep(now)
        plan = self.sched.plan(now)

        trace = self.tracer.enabled
        for req, mode, swap_ids, old_slot, dev_ids in plan.preempt:
            if mode == "swap":
                t0 = self._now() if trace else 0.0
                try:
                    req.ticket = self.store.swap_out(
                        self.caches, old_slot, swap_ids, req.cached_len,
                        dev_ids, skip=len(req.kept_blocks))
                except SwapCopyError:
                    # the copy raised before touching device state: downgrade
                    # to recompute (kept claims + swap blocks released, the
                    # re-prefill rebuilds the KV from tokens)
                    self.stats.swap_faults += 1
                    self.sched.fail_swap_out(req)
                    if trace:
                        self.tracer.instant(
                            "swap-fault", "faults", self._slot_track(old_slot),
                            args={"rid": req.rid, "direction": "out"},
                            flow=req.rid)
                    continue
                self.stats.preempt_swap += 1
                self.stats.swap_skipped_blocks += len(req.kept_blocks)
                if trace:
                    track = self._slot_track(old_slot)
                    self.tracer.span(
                        "swap-copy", "dispatch", track, t0, self._now() - t0,
                        args={"kind": "swap-copy", "direction": "out",
                              "rid": req.rid,
                              "blocks": len(swap_ids) - len(req.kept_blocks),
                              "skipped_blocks": len(req.kept_blocks)},
                        flow=req.rid)
                    self.tracer.flow_event("t", "request", track, req.rid, ts=t0)
            else:
                self.stats.preempt_recompute += 1
                if trace:
                    self.tracer.flow_event("t", "request",
                                           self._slot_track(old_slot), req.rid)
        for req in plan.resume:
            t0 = self._now() if trace else 0.0
            n_swap = len(req.ticket.block_ids)
            try:
                self.caches = self.store.swap_in(self.caches, req.slot,
                                                 req.ticket, req.block_table)
            except SwapCopyError:
                # functional swap-in: the caches are untouched.  Tear the
                # placement back down and requeue as recompute.
                self.stats.swap_faults += 1
                slot = req.slot
                self.sched.fail_resume(req)
                if trace:
                    self.tracer.instant(
                        "swap-fault", "faults", self._slot_track(slot),
                        args={"rid": req.rid, "direction": "in"},
                        flow=req.rid)
                continue
            # endurance mirror: the restore programmed one full block per
            # copied-in device block (retained kept-prefix blocks were never
            # copied — no wear there)
            skip = req.ticket.skip_blocks
            nbl = min(len(req.ticket.block_ids), len(req.block_table) - skip)
            if nbl > 0:
                self.pool.record_writes(
                    [(b, self.block_size)
                     for b in req.block_table[skip:skip + nbl]], self._now())
                self.stats.pool_writes = self.pool.total_writes
            self.store.pool.free(req.ticket.block_ids)
            req.ticket = None
            self._slot_len[req.slot] = req.cached_len
            self._set_last_tok(req.slot, req.generated[-1])
            if self.spec_ngram:
                self._seed_hist(req)
            if trace:
                track = self._slot_track(req.slot)
                self.tracer.span(
                    "swap-copy", "dispatch", track, t0, self._now() - t0,
                    args={"kind": "swap-copy", "direction": "in",
                          "rid": req.rid, "blocks": n_swap},
                    flow=req.rid)
                self.tracer.flow_event("t", "request", track, req.rid, ts=t0)
        for req in plan.admit:
            if self.mixed and not req.extras:
                # mixed dispatch: admission only stages the replay; the
                # prompt runs through fused mixed dispatches below, chunk
                # rows at a time, with decode slots riding along.  Requests
                # carrying extras keep the separate path — the patch-embed
                # overlay needs the whole replay in one dispatch.
                self._stage_mixed_admission(req, plan.grants.get(req.rid))
            else:
                self._prefill_request(req, now, plan.grants.get(req.rid))

        # requests may finish straight out of prefill (max_new == 1)
        for req in list(self.sched.running.values()):
            if req.done:
                self._complete(req, self._now())

        # steady-state pool occupancy sample: distinct device blocks the
        # running tables reference (shared blocks count once)
        held = set()
        for r in self.sched.running.values():
            held.update(r.block_table)
        self.stats.table_block_steps += len(held)
        self.stats.pool_steps += 1
        if trace:
            self.tracer.counter("kv blocks", "pool",
                                {"referenced": len(held),
                                 "used": self.pool.used_blocks,
                                 "free": self.pool.free_blocks})

        # mid-prefill (staged) slots are excluded from every decode path —
        # their cache holds only a replay prefix, so a decode row there
        # would attend over unwritten KV
        active_slots = sorted(
            s for s, r in self.sched.running.items() if not r.prefilling)
        mixed_pending = self.mixed and any(
            r.prefilling for r in self.sched.running.values())
        spec_k = self.spec_ngram
        max_h = self.horizon
        if self.degrade is not None:
            spec_k = self.degrade.spec_k(spec_k)
            max_h = self.degrade.horizon_cap(max_h)
        if nan_ev is not None and not active_slots:
            self.fault_plan.record(nan_ev, "skipped-idle")
        if active_slots and nan_ev is not None:
            # a poisoned step runs the guarded single-step kernel so the
            # NaN is quarantined per-slot; greedy streams are horizon-
            # invariant, so unfaulted co-batched slots stay bit-identical.
            # Mid-prefill slots sit this one step out (the guard has no
            # mixed tile) and resume staging next step.
            return partial(self._decode_guarded_step, active_slots, nan_ev)
        if mixed_pending:
            # ONE dispatch carries decode rows and prefill-chunk rows; the
            # horizon/spec fused paths resume once the prefill burst drains
            return self._dispatch_mixed
        if not active_slots:
            return None
        if spec_k:
            # speculation always rides the fused scan (h == 1 is one
            # draft→verify→accept step); grant 0 ⇒ the pool cannot cover
            # the worst-case K+1-row write span — plain single step
            h = self.sched.grant_horizon(max_h, now, self._est_step_time(),
                                         spec_k=spec_k)
            if h >= 1:
                return partial(self._decode_spec_steps, active_slots, h)
        elif not self.spec_ngram and max_h > 1:
            # (speculation shed by the degradation ladder runs plain single
            # steps, which keep the n-gram history aligned for the restore)
            h = self.sched.grant_horizon(max_h, now, self._est_step_time())
            if h > 1:
                return partial(self._decode_horizon_steps, active_slots, h)
        return partial(self._decode_single_step, active_slots)

    def _decode_single_step(self, active_slots: List[int]) -> None:
        """One ``[slots, 1]`` decode dispatch (the horizon=1 parity baseline)."""
        t0 = self._now()            # engine clock: metrics ≡ stats ≡ trace
        with self._phase("pack"):
            active = np.zeros(self.slots, bool)
            active[active_slots] = True
        with self._phase("tables"):
            tables = self._refresh_tables()  # growth may have extended them
        with self._phase("decode"):
            key = jax.random.fold_in(self._sample_key, self.stats.decode_steps)
            nxt, self.caches, *held = self._decode(
                self.params, self.caches, self._last_tok,
                jnp.asarray(self._slot_len), jnp.asarray(active),
                tables, key, jnp.float32(self.temperature))
        with self._phase("sync"):
            host, held = jax.device_get((nxt, held))  # syncs the step
        wall = self._now() - t0
        now = self._now()
        with self._phase("wear"):
            for s in active_slots:
                self._record_writes(self.sched.running[s],
                                    int(self._slot_len[s]), 1, now)
        with self._phase("emit"):
            self._decode_account(active_slots, active, nxt, t0, wall)
            self._count_moe(held, len(active_slots), self.slots)
            for s in active_slots:
                req = self.sched.running[s]
                self._slot_len[s] += 1
                self.stats.decode_tokens += 1
                self._emit(req, host[s, ..., 0], now)
                if req.done:
                    self._complete(req, now)

    def _decode_account(self, active_slots: List[int], active: np.ndarray,
                        nxt, t0: float, wall: float,
                        guarded: bool = False) -> None:
        """Stats, metrics, trace span and device token state of one
        ``[slots, 1]`` decode dispatch (plain or guarded)."""
        self.stats.decode_time += wall
        self.metrics.observe("dispatch_decode_s", wall)
        if self.tracer.enabled:
            rows = len(active_slots)
            args = {"kind": "decode", "h": 1, "spec_k": 0,
                    "slots_active": rows, "tokens": rows, "rows": rows,
                    "host_syncs": 1,
                    "odin_energy_mj": self.cost_model.energy_mj(rows)}
            if guarded:
                args["guarded"] = True
            self.tracer.span("decode", "dispatch", "dispatch", t0, wall,
                             args=args)
        self.stats.decode_steps += 1
        self.stats.dispatches += 1
        self.stats.decode_dispatches += 1
        self.stats.host_syncs += 1
        self.stats.active_slot_steps += len(active_slots)
        self.stats.slot_steps += self.slots
        self._last_tok = nxt
        if self.spec_ngram:
            # keep the draft history aligned when speculation fell back to a
            # plain step (pool too tight for a verify tile this iteration)
            shifted = jnp.concatenate([self._hist[:, 1:], nxt], axis=1)
            self._hist = jnp.where(jnp.asarray(active)[:, None], shifted,
                                   self._hist)

    def _guarded_fn(self):
        """Lazily-compiled guarded decode step: same math as the plain step
        plus a per-slot finiteness verdict on the last-position logits."""
        if self._guarded is None:
            self._guarded = jax.jit(
                make_serving_decode_guarded(self.cfg, top_k=self.top_k,
                                            sample=self.temperature > 0),
                donate_argnums=(1,))
        return self._guarded

    def _decode_guarded_step(self, active_slots: List[int], ev) -> None:
        """One guarded ``[slots, 1]`` dispatch with an injected NaN poison.

        The poison mask corrupts exactly one slot's logits *post-forward*
        (the PCRAM-drift analog: a resistance excursion flips the readout,
        not the programmed weights).  The guard quarantines that slot as
        FAILED; every other slot samples from untouched logits with the
        same key schedule as the plain step, so unfaulted co-batched greedy
        streams stay bit-identical to a fault-free run."""
        t0 = self._now()            # engine clock: metrics ≡ stats ≡ trace
        with self._phase("pack"):
            active = np.zeros(self.slots, bool)
            active[active_slots] = True
            poison = np.zeros(self.slots, bool)
            target = active_slots[ev.slot % len(active_slots)]
            poison[target] = True
            self.fault_plan.record(ev, "poisoned", slot=target,
                                   rid=self.sched.running[target].rid)
        with self._phase("tables"):
            tables = self._refresh_tables()
        with self._phase("decode"):
            key = jax.random.fold_in(self._sample_key, self.stats.decode_steps)
            nxt, bad, self.caches, *held = self._guarded_fn()(
                self.params, self.caches, self._last_tok,
                jnp.asarray(self._slot_len), jnp.asarray(active),
                tables, key, jnp.float32(self.temperature),
                jnp.asarray(poison))
        with self._phase("sync"):
            host, badh, held = jax.device_get((nxt, bad, held))  # syncs
        wall = self._now() - t0
        now = self._now()
        with self._phase("wear"):
            # the forward wrote each slot's KV row whether or not the logit
            # readout was poisoned — wear is physical, bill it either way
            for s in active_slots:
                self._record_writes(self.sched.running[s],
                                    int(self._slot_len[s]), 1, now)
        with self._phase("emit"):
            self._decode_account(active_slots, active, nxt, t0, wall,
                                 guarded=True)
            self._count_moe(held, len(active_slots), self.slots)
            for s in active_slots:
                req = self.sched.running[s]
                if badh[s]:
                    # quarantine: only the poisoned request fails; its
                    # garbage token never enters a stream and the slot is
                    # re-admittable
                    self.stats.nan_quarantined += 1
                    self._finalize(req, RequestState.FAILED, "nan_logits",
                                   now)
                    continue
                self._slot_len[s] += 1
                self.stats.decode_tokens += 1
                self._emit(req, host[s, ..., 0], now)
                if req.done:
                    self._complete(req, now)

    def _decode_horizon_steps(self, active_slots: List[int], h: int) -> None:
        """One fused dispatch generating up to ``h`` tokens per slot.

        The scheduler has already pre-extended every running table for ``h``
        rows (``grant_horizon``); slots freeze on-device at EOS / budget
        exhaustion, so the returned per-slot ``counts`` tell the host which
        prefix of each slot's ``[h]`` token row is real.  Per-token
        timestamps are linearly interpolated over the dispatch's span *of the
        engine clock* (the host cannot observe inner-step boundaries — that
        is the point; an injected test clock stays self-consistent)."""
        t_before = self._now()      # engine clock: metrics ≡ stats ≡ trace
        active, rem = self._pack_fused(active_slots)
        with self._phase("tables"):
            tables = self._refresh_tables()
        with self._phase("horizon"):
            block, counts, last, self.caches, *held = self._horizon_fn(h)(
                self.params, self.caches, self._last_tok,
                jnp.asarray(self._slot_len), jnp.asarray(active),
                jnp.asarray(rem), tables, self._sample_key,
                jnp.float32(self.temperature),
                jnp.int32(self.stats.decode_steps),
                jnp.int32(-1 if self.eos_id is None else self.eos_id))
        with self._phase("sync"):
            block, counts, held = jax.device_get((block, counts, held))  # ONE sync, h steps
        wall = self._now() - t_before
        now_w = self._now()
        with self._phase("wear"):
            for s in active_slots:
                # endurance mirror: the scan wrote counts[s] KV rows for
                # this slot starting at its pre-dispatch length
                self._record_writes(self.sched.running[s],
                                    int(self._slot_len[s]), int(counts[s]),
                                    now_w)
        with self._phase("emit"):
            self.stats.decode_time += wall
            self.metrics.observe("dispatch_decode_s", wall)
            emitted = int(counts.sum())
            if self.tracer.enabled:
                self.tracer.span(
                    "horizon", "dispatch", "dispatch", t_before, wall,
                    args={"kind": "horizon", "h": h, "spec_k": 0,
                          "slots_active": len(active_slots),
                          "tokens": emitted, "rows": emitted, "host_syncs": 1,
                          "odin_energy_mj": self.cost_model.energy_mj(emitted)})
            self.stats.decode_steps += h
            self.stats.dispatches += 1
            self.stats.decode_dispatches += 1
            self.stats.host_syncs += 1
            self.stats.active_slot_steps += emitted
            self.stats.slot_steps += self.slots * h
            self._count_moe(held, emitted, self.slots * h)
            self._last_tok = last
            span = wall                          # engine-clock dispatch span
            for hh in range(h):                  # step-major: matches h=1 order
                t_h = t_before + (hh + 1) * span / h
                for s in active_slots:
                    if hh < counts[s]:
                        self._slot_len[s] += 1
                        self.stats.decode_tokens += 1
                        self._emit(self.sched.running[s], block[s, ..., hh],
                                   t_h)
            for s in active_slots:
                req = self.sched.running[s]
                if req.done:
                    self._complete(req, t_before + int(counts[s]) * span / h)

    def _pack_fused(self, active_slots: List[int]):
        """The ``pack`` phase of a fused decode: active mask and each slot's
        remaining token budget."""
        with self._phase("pack"):
            active = np.zeros(self.slots, bool)
            active[active_slots] = True
            rem = np.zeros(self.slots, np.int32)
            for s in active_slots:
                rem[s] = self.sched.running[s].remaining
        return active, rem

    def _decode_spec_steps(self, active_slots: List[int], h: int) -> None:
        """One fused dispatch of ``h`` draft→verify→accept inner steps.

        Each inner step emits 1..K+1 tokens per live slot (the accepted
        draft prefix plus the bonus token); ``counts[s, hh]`` tells the host
        which prefix of ``block[s, hh]`` is real.  Timestamps interpolate
        over the dispatch's engine-clock span per inner step, and within a
        step across its accepted run."""
        K = self.spec_ngram
        t_before = self._now()      # engine clock: metrics ≡ stats ≡ trace
        active, rem = self._pack_fused(active_slots)
        with self._phase("tables"):
            tables = self._refresh_tables()
        with self._phase("spec-horizon"):
            block, counts, last, hist, self.caches, *held = self._fused_fn(h, K)(
                self.params, self.caches, self._last_tok,
                jnp.asarray(self._slot_len), jnp.asarray(active),
                jnp.asarray(rem), self._hist, tables,
                jnp.int32(-1 if self.eos_id is None else self.eos_id))
        with self._phase("sync"):
            block, counts, held = jax.device_get((block, counts, held))  # ONE sync
        wall = self._now() - t_before
        now_w = self._now()
        live = counts > 0                                  # [slots, h]
        with self._phase("wear"):
            for s in active_slots:
                # endurance mirror: every live inner step wrote a K+1-row
                # verify tile at the slot's running position (rejected rows
                # were physically written before rollback — their wear is
                # real), and the position advanced by the accepted count
                pos = int(self._slot_len[s])
                for hh in range(h):
                    if live[s, hh]:
                        self._record_writes(self.sched.running[s], pos,
                                            K + 1, now_w)
                        pos += int(counts[s, hh])
        with self._phase("emit"):
            self._last_tok = last
            self._hist = hist
            self.stats.decode_time += wall
            self.metrics.observe("dispatch_decode_s", wall)
            self.stats.decode_steps += h
            self.stats.dispatches += 1
            self.stats.decode_dispatches += 1
            self.stats.host_syncs += 1
            self.stats.active_slot_steps += int(live.sum())
            self.stats.slot_steps += self.slots * h
            self.stats.spec_drafted += K * int(live.sum())
            self.stats.spec_accepted += int((counts - live).sum())
            # every live inner step verified a K+1-row forward; rows beyond
            # the emitted run are rejected drafts — real PIMC energy, billed
            # as verify overhead both fleet-wide and on the request that
            # incurred them
            emitted = int(counts.sum())
            rows = (K + 1) * int(live.sum())
            self.stats.spec_overhead_rows += rows - emitted
            self._count_moe(held, rows, self.slots * (K + 1) * h)
            for s in active_slots:
                s_over = int(((K + 1) * live[s] - counts[s]).sum())
                if s_over:
                    self.sched.running[s].spec_overhead_rows += s_over
            if self.tracer.enabled:
                self.tracer.span(
                    "spec-horizon", "dispatch", "dispatch", t_before, wall,
                    args={"kind": "spec-horizon", "h": h, "spec_k": K,
                          "slots_active": len(active_slots),
                          "tokens": emitted,
                          "drafted": K * int(live.sum()),
                          "accepted": int((counts - live).sum()),
                          "rows": rows, "overhead_rows": rows - emitted,
                          "host_syncs": 1,
                          "odin_energy_mj": self.cost_model.energy_mj(rows)})
            span = wall
            last_t = {}
            for hh in range(h):                  # step-major: matches h=1 order
                for s in active_slots:
                    m = int(counts[s, hh])
                    for j in range(m):
                        t_tok = t_before + (hh + (j + 1) / m) * span / h
                        self._slot_len[s] += 1
                        self.stats.decode_tokens += 1
                        self._emit(self.sched.running[s], block[s, hh, j],
                                   t_tok)
                        last_t[s] = t_tok
            for s in active_slots:
                req = self.sched.running[s]
                if req.done:
                    self._complete(req, last_t.get(s, t_before + span))

    def _horizon_fn(self, h: int) -> Callable:
        return self._fused_fn(h, 0)

    def _fused_fn(self, h: int, k: int) -> Callable:
        """LRU cache of compiled fused decode executables, keyed (h, k)."""
        key = (h, k)
        fn = self._fused.get(key)
        if fn is None:
            if k:
                fn = jax.jit(
                    make_serving_spec_horizon(self.cfg, h, k, n=self._spec_n),
                    donate_argnums=(1,))
            else:
                fn = jax.jit(
                    make_serving_decode_horizon(self.cfg, h, top_k=self.top_k,
                                                sample=self.temperature > 0),
                    donate_argnums=(1,))
            self._fused[key] = fn
            if len(self._fused) > self.jit_cache:
                self._fused.popitem(last=False)
                self.stats.jit_evictions += 1
        else:
            self._fused.move_to_end(key)
        return fn

    def _est_step_time(self) -> float:
        """Measured seconds per decode token step (0 until the first step)."""
        return (self.stats.decode_time / self.stats.decode_steps
                if self.stats.decode_steps else 0.0)

    def run(self, requests: Sequence[Request] = (), max_steps: int = 100_000) -> Dict:
        """Submit ``requests``, drive the loop until drained, return the
        metrics summary (per-request records + aggregates)."""
        for req in requests:
            self.submit(req)
        self._now()                                       # start the clock
        steps = idle = 0
        while self.sched.has_work:
            busy = bool(self.sched.running)
            self.step()
            if busy or self.sched.running:
                steps += 1
                idle = 0
                if steps > max_steps:
                    raise EngineStallError(
                        f"engine exceeded {max_steps} steps",
                        summary=self.summary())
            else:
                # idle: nothing running, next arrival in the future.  Idle
                # waits don't count against the runaway-loop bound (a
                # low-rate open-loop workload may idle for minutes), but
                # they are bounded too in case an injected clock never
                # advances past the next arrival.
                idle += 1
                if idle > max_steps:
                    raise EngineStallError(
                        f"engine idle for {max_steps} iterations — is the "
                        "clock advancing toward the next arrival?",
                        summary=self.summary())
                nxt = self.sched.next_arrival()
                if nxt is not None and nxt > self._now():
                    # an injected ticking clock can advance between the
                    # check above and this read — never sleep negative
                    time.sleep(max(0.0, min(0.05, nxt - self._now())))
        return self.summary()

    def summary(self) -> Dict:
        done = self._all_requests()
        self._update_wear_gauges()
        self.stats.retired_blocks = len(self.pool.retired)
        self.metrics.flush(self._now(), self._counter_snapshot())
        out = summarize(done, self.stats, self.cost_model,
                        registry=self.metrics)
        if self.degrade is not None:
            # full controller state: transition history plus the live
            # retry_after_s hint (None unless admissions are denied now)
            out["degradation"].update(self.degrade.snapshot(self._now()))
        if self.fault_plan is not None:
            out["fault_plan"] = self.fault_plan.snapshot()
        return out

    def _all_requests(self) -> List[Request]:
        seen = {r.rid: r for _, _, r in self.sched.waiting}
        for r in list(self.sched.swapped) + list(self.sched.running.values()):
            seen[r.rid] = r
        for r in self._done:
            seen[r.rid] = r
        return list(seen.values())
