"""Continuous-batching serving engine over a paged KV-cache pool.

The batch-offline ``InferenceEngine.generate`` compiles one program per
``(batch, prompt_len, max_new_tokens)`` shape and runs every sequence
lock-step to the longest; this engine instead keeps ONE resident compiled
MIXED step whose shapes never change and serves arbitrary request mixes by
changing only the DATA it feeds that step. The design follows "Ragged
Paged Attention" (arxiv 2604.15464) end to end: the step's token axis is a
flat PACKED batch — one decode token per running resident plus this step's
budgeted prefill chunks, laid out as contiguous per-slot segments — and
raggedness (segment offsets/lengths, chunk starts, context lengths, block
tables) rides scalar descriptors, never the compiled shape. Decode rows
and prefill chunks run on the SAME attention grid
(``ops/pallas/ragged_attention.py``), so there is no sentinel-row waste
for mid-prefill slots, no second resident compile, and no prefill/decode
scheduling seam: heavy mixed traffic is one device dispatch per step and
never recompiles.

Per :meth:`ServingEngine.step` (the default unified path):

1. **admit** — FIFO queue head(s) get a slot + pages (prefix-cache hits
   acquire cached pages); their prompt starts consuming the step's prefill
   token budget as packed chunk segments;
2. **grow/preempt** — every decoding sequence is guaranteed a page for the
   token this step appends; when the pool is dry the lowest-priority
   most-recently-admitted sequence is evicted back to the queue front
   (recompute-style);
3. **mixed step** — the single jitted program appends every packed token's
   KV through its row's block table, attends decode rows (1 query at
   ``context - 1``) and chunk rows (n queries from ``chunk_start``) on one
   ragged grid, and samples each row's last-position token; decode rows
   harvest it, a final chunk harvests token one (TTFT ends there), and
   finished sequences release slot + pages the same step.

``ServingConfig.mixed_step=False`` keeps the PREVIOUS two-program engine
(ragged decode over ``max_batch_size`` slots + a ``[1, chunk]`` chunked
prefill, with bucketed monolithic prefill when chunking is off) — kept so
benchmarks and parity tests can A/B the unified step against it in the
same run; new deployments should not use it.

Compile counts are instrumented (the trace-time counter in
``compile_counts``) so tests can assert the whole mixed-traffic run used
exactly ONE compiled serving step (``{"mixed_step": 1}``).

Overload control and fault recovery (the resilience contract):

- **deadlines** — ``submit(..., deadline_s=)``; queued requests past
  deadline are shed at the admission gate, running ones end in terminal
  ``TIMEOUT`` with their pages returned;
- **admission control** — bounded queue depth + KV-headroom gate; rejects
  raise :class:`RejectedError` (or ``try_submit`` returns None); a
  higher-priority submit displaces the lowest-priority queued request
  instead of being rejected;
- **graceful degradation** — preemption and shedding take lowest-priority
  newest work first; a brownout (manual or occupancy-triggered) caps every
  admission's token budget; ``drain()`` stops admitting, sheds the queue
  and finishes residents;
- **step watchdog + output guard** — a wall-clock watchdog thread bounds
  the resident decode step (a wedged/slow step fails ITS requests and the
  engine keeps serving; abandoned results are discarded — the watchdog
  forces pool donation off so that is always safe — and while the
  abandoned thread is still wedged no new one is stacked), and a NaN/Inf
  logit guard quarantines the offending request instead of poisoning the
  batch;
- **chaos points** — ``DS_FAULT=stall|slow_step|corrupt_logits|
  flaky_prefill`` (plus ``p=`` probabilistic variants) exercise all of the
  above; the chaos suite asserts every request reaches a terminal state
  and zero pages leak under any injected fault.
"""

import contextlib
import dataclasses
import os
import threading
import time
import weakref
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.layers import harvest_packed_logits, paged_cache_index
from ...monitor.perf import (PerfAccounting, estimate_decode_step_bytes,
                             estimate_decode_step_flops, param_bytes,
                             transformer_flops_per_token)
from ...monitor.tracing import FlightRecorder, Tracer, dump_seq, versioned
from ...utils import fault_injection
from ...utils.logging import log_dist
from ..engine import InferenceEngine, _sample_logits, next_pow2
from .block_pool import BlockPool, BlockPoolError, chain_hash
from .metrics import ServingMetrics
from .scheduler import RejectedError, Request, RequestState, Scheduler


class StepWatchdogTimeout(RuntimeError):
    """A resident serving step exceeded ``step_watchdog_s`` wall-clock."""


@dataclasses.dataclass
class _Promotion:
    """One in-flight host->device promotion: a request's WHOLE matched
    host prefix as one device_put'd payload (one transfer, one fold
    dispatch — per-page folds would pay one functional pool update
    each), plus enough identity to validate the fold targets — the
    request's CURRENT admission segment and the exact page ids it was
    granted (a preempted/terminal request's pages are back in the pool
    and may already belong to someone else). ``width`` is the pow2 the
    payload was padded to (by repeating the last page — duplicate
    scatter targets with identical updates are deterministic), so the
    fold program compiles once per width, a set bounded by
    log2(max pages per sequence)."""
    req: "Request"
    block_idxs: List[int]
    dst_bids: List[int]
    arr: Any
    width: int
    admit_order: int
    t_sched: float


def _tree_ready(tree) -> bool:
    """Has every leaf of a device_put'd pytree landed on device? Leaves
    without ``is_ready`` (plain numpy on odd paths) count as landed —
    the fold would at worst block briefly, never corrupt."""
    return all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "is_ready"))


#: live engines in this process (weak — a dropped engine vanishes);
#: ``ds_report`` reads speculation status from here, next to the
#: compiled-program table that is per-process for the same reason.
#: The lock mirrors ``monitor/perf.py``'s ``_live_registries`` pattern:
#: WeakSet iteration runs Python-level bytecode, so ``list(ws)`` on the
#: report thread races an ``add`` from a thread constructing an engine
#: (``RuntimeError: Set changed size during iteration``).
_live_engines_lock = threading.Lock()
_LIVE_ENGINES: "weakref.WeakSet" = weakref.WeakSet()  # dslint: guarded-by=_live_engines_lock


def live_serving_engines() -> List["ServingEngine"]:
    """Strong refs to every live ServingEngine in this process."""
    with _live_engines_lock:
        return list(_LIVE_ENGINES)


@dataclasses.dataclass
class ServingConfig:
    """Knobs of the serving layer (the inference config keeps model-level
    ones: dtype, quantize, ``kv_cache_int8``, mp/ep)."""

    #: decode slots — the fixed batch of the resident decode step
    max_batch_size: int = 8
    #: tokens per KV page
    block_size: int = 16
    #: pages in the shared pool (total KV capacity = num_blocks * block_size)
    num_blocks: int = 256
    #: per-sequence cap on prompt + generated tokens; also fixes the block
    #: table width (ceil(max_model_len / block_size))
    max_model_len: int = 512
    #: ONE resident serving program (the default): decode rows and prefill
    #: chunks packed into a single ragged token batch per step — no
    #: sentinel decode rows, no second resident compile, one device
    #: dispatch per step. False = the LEGACY two-program engine (resident
    #: decode + chunked prefill / bucketed monolithic prefill), whose only
    #: users are its parity tests (ROADMAP D2).
    mixed_step: bool = True
    # sampling (static per engine: they shape the compiled programs)
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    #: smallest prefill bucket (prompt lengths pad up to powers of two from
    #: here; each bucket compiles once). Only the LEGACY
    #: (``mixed_step=False``, chunking off) monolithic prefill uses
    #: buckets; the unified step needs no prefill program at all.
    prefill_bucket_min: int = 8
    # -- prefix caching + chunked prefill ------------------------------
    #: content-addressed KV reuse: full pages are indexed by a hash chained
    #: over the token prefix; admission matches each prompt's longest
    #: cached prefix, reuses those pages (copy-on-write on divergence) and
    #: prefills only the suffix. Unreferenced pages are kept warm and
    #: evicted LRU instead of blanked. Implies chunked prefill (the
    #: from-empty monolithic prefill cannot attend a cached prefix).
    prefix_cache: bool = False
    #: prefill chunk length in tokens — with ``mixed_step`` the per-row
    #: per-round granularity of budget packing (fairness knob; a row may
    #: accumulate several rounds); legacy: the compiled ``[1, chunk]``
    #: chunked-prefill shape (0 there = monolithic bucketed prefill).
    #: 0 derives 4 * block_size on the unified path (legacy derives it
    #: only with prefix_cache on); the config object is never mutated.
    prefill_chunk_tokens: int = 0
    #: per-step prefill token budget of the mixed step: at most this many
    #: prompt tokens run per step, so resident decoders keep stepping
    #: every iteration (no prefill head-of-line blocking). With
    #: ``mixed_step`` it also sizes the packed token batch
    #: (``max_batch_size - 1 + budget``). 0 = one chunk's worth per step.
    prefill_token_budget: int = 0
    # -- speculative decoding (serving/speculative.py) ------------------
    #: max drafted tokens per resident per step (0 = speculation off).
    #: A speculating resident packs a VERIFY row (``query_len = k + 1``)
    #: instead of its T=1 decode row — same resident program, same one
    #: dispatch — and commits up to ``k + 1`` tokens when the target
    #: model's greedy predictions confirm the drafts. Verify rows spend
    #: the packed step's LEFTOVER capacity only: prefill grants and the
    #: one guaranteed decode token per resident always outrank them, so
    #: speculation degrades to plain decode under prefill pressure
    #: instead of starving admissions. Requires the unified
    #: ``mixed_step`` engine and greedy sampling (``do_sample=False`` —
    #: the accept rule compares greedy argmax predictions).
    spec_tokens: int = 0
    #: longest n-gram the default prompt-lookup drafter matches against
    #: the resident's own prompt + generated history (it falls back to
    #: shorter n-grams down to 1; no match = no draft = plain decode)
    spec_ngram: int = 3
    #: pluggable drafter (``serving.speculative.Drafter``); None with
    #: ``spec_tokens > 0`` builds the model-free
    #: :class:`~.speculative.PromptLookupDrafter` — a small draft model
    #: can implement the same interface later. The engine never mutates
    #: it, so one instance may serve several engines.
    drafter: Optional[Any] = None
    # -- tiered KV cache (serving/kv_tiers.py) --------------------------
    #: host-RAM spill tier capacity in KV pages (0 = no tier). With a
    #: tier attached, pool evictions DEMOTE (page copied host-side,
    #: content chain preserved) instead of destroying, admission's
    #: longest-prefix match extends into the host index, and matched
    #: host pages stream back up via async promotion overlapping the
    #: uncached-suffix prefill. Requires ``prefix_cache``.
    host_cache_blocks: int = 0
    #: host-tier byte budget (None = unbounded; combines with the block
    #: cap — whichever is hit first evicts the tier's own LRU)
    host_cache_bytes: Optional[int] = None
    #: fold every promotion synchronously at admission instead of
    #: pumping the queue asynchronously — the A/B control for the
    #: promotion-overlap benchmark; production keeps this False
    sync_promote: bool = False
    #: opt-in pow2-bucketed packed widths for the mixed step: instead of
    #: every step paying the full ``[1, max_batch_size - 1 + budget]``
    #: padded token batch (decode-only steps on the XLA reference path
    #: compute mostly padding), the engine compiles a small bounded set
    #: of widths (pow2 steps from ``max_batch_size`` up to the full
    #: capacity) and dispatches the narrowest bucket that fits the
    #: step's packed rows. ``compile_counts["mixed_step"]`` is then
    #: bounded by the bucket count (instead of exactly 1) and the
    #: recompile sentinel learns one fingerprint per bucket. Default off:
    #: the strict one-compile invariant stays the default contract.
    mixed_step_buckets: bool = False
    #: write serving counters to the monitor every N steps (0 = never)
    monitor_every: int = 1
    # -- overload control / resilience ---------------------------------
    #: queued requests beyond this are rejected (0 = unbounded); a
    #: higher-priority submit displaces the lowest-priority queued request
    #: instead of bouncing
    max_queue_depth: int = 0
    #: KV-headroom admission gate: keep at least this many pool blocks
    #: clear of committed demand (used pages + every queued prefill + the
    #: newcomer's prefill); None disables the gate
    kv_headroom_blocks: Optional[int] = None
    #: deadline applied to submits that do not pass their own (seconds
    #: from submit; None = no deadline)
    default_deadline_s: Optional[float] = None
    #: brownout auto-engages when pool occupancy reaches this fraction
    #: (None = only via set_brownout(True))
    brownout_occupancy: Optional[float] = None
    #: token budget cap applied to admissions while browned out
    brownout_max_new_tokens: int = 8
    #: wall-clock budget for one resident decode step; past it the step's
    #: requests fail and serving continues (0 = no watchdog)
    step_watchdog_s: float = 0.0
    #: quarantine requests whose logits go NaN/Inf instead of emitting
    #: garbage tokens
    logit_guard: bool = True
    # -- SLO / goodput --------------------------------------------------
    #: time-to-first-token SLO (seconds, submit -> first token); a
    #: finished request past it is attributed ``ttft_miss``. None = every
    #: finished request is latency-``good`` (availability verdicts —
    #: shed/failed — are still attributed)
    ttft_slo_s: Optional[float] = None
    #: time-per-output-token SLO (seconds/token over the decode phase);
    #: a finished request whose mean inter-token latency exceeds it is
    #: attributed ``tpot_miss``
    tpot_slo_s: Optional[float] = None
    # -- tracing / flight recorder -------------------------------------
    #: record span timelines (per-request phases, prefill chunks, decode
    #: steps, compiles) into a bounded in-memory ring; export with
    #: :meth:`ServingEngine.dump_trace`. Disabled tracing costs one
    #: attribute check per emission site and allocates nothing.
    trace: bool = False
    #: directory for trace dumps + flight-recorder post-mortems; setting
    #: it implies ``trace`` (watchdog trips and logit quarantines then
    #: dump the last trace events + a metrics snapshot here)
    trace_dir: Optional[str] = None
    #: ring-buffer capacity in events (memory bound under any traffic)
    trace_capacity: int = 8192
    #: trace events included in each flight-recorder dump
    flight_events: int = 512


@dataclasses.dataclass
class RequestOutput:
    rid: str
    state: str
    prompt: List[int]
    tokens: List[int]
    finish_reason: Optional[str]
    ttft_s: Optional[float]
    preemptions: int


class ServingEngine:
    """Continuous-batching front end. Construct from an
    :class:`InferenceEngine` (or via :func:`init_serving`); drive with
    :meth:`submit` / :meth:`poll` / :meth:`stream` / :meth:`run`."""

    def __init__(self, engine: InferenceEngine,
                 config: Optional[ServingConfig] = None, monitor=None):
        if not isinstance(engine, InferenceEngine):
            raise TypeError("ServingEngine wraps an InferenceEngine; use "
                            "init_serving(...) to build both")
        if not hasattr(engine.module, "init_paged_cache"):
            raise TypeError(
                f"{type(engine.module).__name__} has no init_paged_cache: "
                "paged serving supports the Llama and GPT-2 families")
        self.engine = engine
        self.config = config or ServingConfig()
        self.monitor = monitor
        cfg = self.config
        if cfg.max_model_len % cfg.block_size:
            raise ValueError("max_model_len must be a multiple of block_size")

        if cfg.prefill_chunk_tokens < 0 or cfg.prefill_token_budget < 0:
            # a negative budget would be truthy and silently disable
            # chunking: admitted requests would sit 'prefilling' forever
            # and run() would never return — reject at construction like
            # the other knobs
            raise ValueError(
                "prefill_chunk_tokens and prefill_token_budget must be "
                ">= 0 (0 = default)")
        # chunk length (unified: the budget-packing granularity; legacy:
        # the resident chunked-prefill shape, 0 = monolithic bucketed
        # prefill) and the per-step prefill token budget — derived, never
        # written back into the caller's (possibly shared) config object
        self._mixed = bool(cfg.mixed_step)
        chunk = cfg.prefill_chunk_tokens
        if chunk <= 0 and (self._mixed or cfg.prefix_cache):
            chunk = 4 * cfg.block_size
        self._chunk = min(chunk, cfg.max_model_len) if chunk > 0 else 0
        self._chunk_budget = cfg.prefill_token_budget or self._chunk
        # packed token capacity of the unified step: every slot may decode
        # (1 token each) OR — when at least one slot is mid-prefill — up
        # to max_batch_size - 1 decoders plus the whole prefill budget
        self._mixed_tokens = max(cfg.max_batch_size,
                                 cfg.max_batch_size - 1 + self._chunk_budget)

        # -- speculative decoding: drafter + verify-row bookkeeping -----
        if cfg.spec_tokens < 0:
            raise ValueError("spec_tokens must be >= 0 (0 = off)")
        self._drafter = None
        if cfg.spec_tokens > 0:
            if not self._mixed:
                raise ValueError(
                    "speculative decoding needs the unified mixed step "
                    "(mixed_step=True): verify rows are packed ragged "
                    "segments of the one resident program")
            if cfg.do_sample:
                raise ValueError(
                    "speculative decoding requires greedy sampling "
                    "(do_sample=False): the accept rule compares the "
                    "target model's argmax predictions against the "
                    "drafts token for token")
            if cfg.drafter is not None:
                self._drafter = cfg.drafter
            else:
                from .speculative import PromptLookupDrafter

                self._drafter = PromptLookupDrafter(cfg.spec_ngram)

        # -- bucketed packed widths (opt-in; see mixed_step_buckets) ----
        self._bucket_widths: Optional[List[int]] = None
        if cfg.mixed_step_buckets:
            if not self._mixed:
                raise ValueError("mixed_step_buckets needs mixed_step=True")
            ws: List[int] = []
            w = next_pow2(max(1, cfg.max_batch_size))
            while w < self._mixed_tokens:
                ws.append(w)
                w *= 2
            ws.append(self._mixed_tokens)
            self._bucket_widths = ws
        # the adaptive draft cap trades draft length for a NARROWER
        # dispatch, so it only engages where width actually costs:
        # bucketed packed widths (narrower bucket = less padded compute)
        # or the Pallas kernel (per live q-tile). On the fixed-width
        # XLA reference path a rejected draft occupies padding the step
        # computes either way — shrinking there would only suppress
        # commits. The packed-capacity slack bound applies everywhere.
        mcfg = getattr(engine.module, "config", None)
        self._spec_adaptive = self._bucket_widths is not None or \
            getattr(mcfg, "decode_attention_impl", None) == "pallas"

        # tracing first: scheduler and pool take the tracer at construction
        # (NULL-like when disabled — emission sites cost one bool check)
        self.tracer = Tracer(capacity=cfg.trace_capacity,
                             enabled=bool(cfg.trace or cfg.trace_dir))
        self.nb_max = cfg.max_model_len // cfg.block_size
        self.block_pool = BlockPool(cfg.num_blocks, cfg.block_size,
                                    tracer=self.tracer)
        self.sched = Scheduler(cfg.max_batch_size, self.block_pool,
                               self.nb_max, prefix_cache=cfg.prefix_cache,
                               tracer=self.tracer)
        self.metrics = ServingMetrics(blocks_total=cfg.num_blocks)
        #: SLO attribution: every terminal transition (including gate-side
        #: sheds that never pass through an engine method) funnels through
        #: Scheduler._release, which calls this hook before emitting the
        #: terminal span — so the verdict rides the span and the goodput
        #: gauges see every request exactly once
        self.sched.on_terminal = self._slo_on_terminal
        #: performance accounting: compiled-program registry + recompile
        #: sentinel (the runtime alarm behind the "ONE decode compile"
        #: invariant), cost-model FLOPs/bytes, MFU/MBU math, and HBM
        #: watermark sampling. Alarm counters land in the metrics registry.
        self.perf = PerfAccounting(
            tracer=self.tracer, metrics=self.metrics.registry,
            scope="serving",
            n_devices=int(np.prod(engine.mesh.devices.shape)))
        #: post-mortem capture: armed iff trace_dir is set — watchdog
        #: trips, logit quarantines and DS_FAULT firings each dump the
        #: last trace events + a metrics snapshot there
        self.flight: Optional[FlightRecorder] = None
        if cfg.trace_dir:
            self.flight = FlightRecorder(cfg.trace_dir, self.tracer,
                                         metrics_fn=self.metrics.snapshot,
                                         last_n=cfg.flight_events)
            self.flight.arm_faults()

        kv_dtype = jnp.int8 if engine.config.kv_cache_int8 \
            else engine.compute_dtype
        self._kv_bytes_per_elem = jnp.dtype(kv_dtype).itemsize
        # committed REPLICATED over the engine mesh: the serving programs
        # declare replicated in_shardings for the pool (TP shards only the
        # params), and a single-device-committed pool would conflict
        self.pool = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, engine._replicated),
            engine.module.init_paged_cache(cfg.num_blocks, cfg.block_size,
                                           dtype=kv_dtype))

        # -- tiered KV: host-RAM spill tier behind the pool's LRU -------
        self.host_tier = None
        if cfg.host_cache_blocks or cfg.host_cache_bytes is not None:
            if cfg.host_cache_blocks < 0:
                raise ValueError("host_cache_blocks must be >= 0")
            if not cfg.prefix_cache:
                raise ValueError(
                    "the host KV tier extends the prefix cache "
                    "(demoted pages are matched by content chain): set "
                    "prefix_cache=True with host_cache_blocks/bytes")
            from .kv_tiers import HostTier, fetch_paged_blocks

            self.host_tier = HostTier(max_blocks=cfg.host_cache_blocks,
                                      max_bytes=cfg.host_cache_bytes,
                                      tracer=self.tracer)
            # the reader reads self.pool at CALL time (the engine rebinds
            # the pool tree every step), so demotion always copies the
            # current page content; a whole eviction wave is ONE read
            self.block_pool.attach_host_tier(
                self.host_tier,
                lambda bids: fetch_paged_blocks(self.pool, bids))
        #: in-flight promotions (scheduled host->device transfers not yet
        #: folded into the pool). Engine-thread owned; the scrape path
        #: sees only the promote_queue_depth gauge written at step
        #: bookkeeping, and pump/schedule snapshot-swap before iterating
        self._promote_q: List[Any] = []  # dslint: guarded-by=snapshot
        #: fold programs keyed by pow2 page width (bounded by
        #: log2(pages per sequence) — never observed as a serving
        #: program: promotion is pool plumbing, not a resident step)
        self._insert_fns: Dict[int, Any] = {}
        #: widths whose first fold (carrying the XLA compile) already
        #: ran — later folds are watchdog-judged (first-beat rule)
        self._promote_warm: "set[int]" = set()

        B = cfg.max_batch_size
        self._tables = np.full((B, self.nb_max), self.block_pool.sentinel,
                               np.int32)
        self._seq_lens = np.zeros((B,), np.int32)
        self._last_tok = np.zeros((B,), np.int32)

        self._requests: Dict[str, Request] = {}
        self._rng = jax.random.PRNGKey(cfg.seed)
        #: name of this engine's probabilistic DS_FAULT stream (None =
        #: the process-global stream). The fleet wires each replica to
        #: its own (``Replica.__init__``) so a p= fault's firing
        #: sequence is derived per replica from (DS_FAULT_SEED, stream)
        #: — one replica's probe cadence can never perturb another's,
        #: and a fuzz schedule replays per-replica regardless of how
        #: the router interleaves steps
        self.fault_stream: Optional[str] = None
        self._step_no = 0
        self._draining = False
        #: manual brownout override: None = automatic (occupancy), else forced
        self._brownout_forced: Optional[bool] = None
        #: trace-time counters — a retrace IS a recompile, so these count
        #: XLA compiles of each program kind. The unified engine has ONE
        #: resident program; the legacy keys exist only in legacy mode (a
        #: retired ``chunked_prefill`` entry must read as gone, not as 0)
        self.compile_counts = (  # dslint: guarded-by=snapshot
            {"mixed_step": 0} if self._mixed
            else {"decode": 0, "prefill": 0, "chunked_prefill": 0})
        #: first mixed/decode/chunked-prefill call carries the XLA compile
        #: and is never watchdog-judged (heartbeat.py's first-beat rule).
        #: With bucketed widths each bucket's first call carries its OWN
        #: compile, so warmth is tracked per width (``_warm_widths``);
        #: ``_mixed_warm`` stays the readiness bit (ever dispatched).
        self._mixed_warm = False
        self._warm_widths: "set[int]" = set()
        self._decode_warm = False
        self._chunked_warm = False
        #: the one abandoned watchdog thread, if still wedged in device
        #: compute — bounds thread growth to 1 under a persistent hang.
        #: Written only by the engine thread; the /healthz probe thread
        #: reads it, so probe-side reads must snapshot to a local first
        self._wedged: Optional[threading.Thread] = None  # dslint: guarded-by=snapshot
        #: incident recency for the /healthz probe (perf_counter stamps;
        #: None = never happened)
        self._last_trip_time: Optional[float] = None
        self._last_quarantine_time: Optional[float] = None
        #: resident mixed-step executables keyed by packed width (one
        #: entry — the full capacity — unless mixed_step_buckets)
        self._mixed_fns: Dict[int, Any] = {}
        self._decode_fn = None
        self._prefill_fns: Dict[int, Any] = {}
        self._chunked_prefill_fn = None
        self._defrag_fn = None
        self._copy_blocks_fn = None
        # donation lets XLA update the pool in place on TPU; CPU would only
        # warn that donation is unimplemented. With the step watchdog armed
        # donation stays OFF even on TPU: an abandoned (timed-out) step must
        # be discardable, which needs functional — not in-place — pool
        # updates; the price is one pool copy per step.
        self._donate = (1,) if jax.default_backend() != "cpu" \
            and not cfg.step_watchdog_s else ()
        with _live_engines_lock:
            _LIVE_ENGINES.add(self)
        log_dist(f"ServingEngine: slots={B}, pool={cfg.num_blocks}x"
                 f"{cfg.block_size} ({kv_dtype.__name__ if hasattr(kv_dtype, '__name__') else kv_dtype}), "
                 f"max_len={cfg.max_model_len}"
                 + (f", spec={self._drafter.kind} k<={cfg.spec_tokens}"
                    if self._drafter is not None else ""), ranks=[0])

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: int = 16,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0) -> str:
        """Enqueue a request; returns its id (admission is FIFO within a
        priority). Raises :class:`RejectedError` when admission control
        refuses the request (queue full / KV headroom / draining) — use
        :meth:`try_submit` for a non-raising variant. ``deadline_s`` is a
        total-latency budget from now; a request still queued or decoding
        past it ends in terminal ``TIMEOUT``."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        # coerce EVERY caller-supplied field up front: a malformed argument
        # must raise before the admission gates shed displacement victims
        max_new_tokens = int(max_new_tokens)
        priority = int(priority)
        if deadline_s is not None:
            deadline_s = float(deadline_s)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.config.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_model_len={self.config.max_model_len}")
        # per-sequence page-cap validation BEFORE the admission gates: a
        # caller error must never fire after displacement victims were
        # already shed (the scheduler re-checks as a backstop)
        need_cap = self.block_pool.blocks_for_tokens(
            len(prompt) + max_new_tokens)
        if need_cap > min(self.nb_max, self.block_pool.num_blocks):
            raise ValueError(
                f"request needs {need_cap} KV blocks at its length cap; "
                f"the pool serves at most "
                f"{min(self.nb_max, self.block_pool.num_blocks)} per "
                f"sequence (raise num_blocks/max_model_len)")
        cfg = self.config
        tr = self.tracer
        if self._draining:
            self.metrics.requests_rejected += 1
            if tr.enabled:
                tr.instant("reject", cat="sched", args={"reason": "draining"})
            raise RejectedError("draining", "engine is draining; "
                                "no new admissions")
        # Both admission gates honor priority displacement: a newcomer that
        # outranks queued work sheds it (lowest priority first, newest
        # within a tier) rather than being rejected. Victims for BOTH
        # gates are selected as a DRY RUN and only cancelled once the
        # newcomer is known to pass every gate — a reject must never
        # destroy queued work.
        victims: List[Request] = []
        displaceable = self.sched.displaceable(priority)
        # hash the newcomer's full blocks ONCE: the headroom gate and the
        # Request both consume these keys (scheduler.submit skips
        # rehashing when they are already set)
        prompt_hashes = self.block_pool.prefix_block_hashes(prompt) \
            if cfg.prefix_cache else None
        if cfg.kv_headroom_blocks is not None:
            budget = self.block_pool.num_blocks - cfg.kv_headroom_blocks
            # every request is charged the pages its admission takes OUT
            # of the allocatable pool: uncached suffix + cached
            # (refcount-0) matched pages it would pin, deduplicated across
            # the whole scan (a page N sharers match pins once) —
            # already-referenced matches are in used_count and charged to
            # nobody twice. Each shed victim RE-RUNS the scan without it
            # instead of subtracting its charge: a shared pin charged to
            # the victim may still be pinned by a surviving sharer, and a
            # plain subtraction would credit it anyway (silently violating
            # the headroom guarantee). Sheds are rare; the scan is cheap.
            it = iter(displaceable)
            while True:
                charges, newcomer = self.sched.admission_charges(
                    newcomer_len=len(prompt),
                    newcomer_hashes=prompt_hashes,
                    exclude={v.rid for v in victims})
                demand = (self.block_pool.used_count
                          + sum(charges.values()) + newcomer)
                if demand <= budget:
                    break
                v = next(it, None)
                if v is None:
                    break
                victims.append(v)
            if demand > budget:
                self.metrics.requests_rejected += 1
                if tr.enabled:
                    tr.instant("reject", cat="sched",
                               args={"reason": "kv_headroom",
                                     "demand": int(demand),
                                     "budget": int(budget)})
                raise RejectedError(
                    "kv_headroom", f"committed KV demand {demand} "
                    f"blocks exceeds admission budget {budget} "
                    f"(pool {self.block_pool.num_blocks} - headroom "
                    f"{cfg.kv_headroom_blocks})")
        if cfg.max_queue_depth and \
                self.sched.queue_depth - len(victims) >= cfg.max_queue_depth:
            extra = next((v for v in displaceable if v not in victims), None)
            if extra is None:
                self.metrics.requests_rejected += 1
                if tr.enabled:
                    tr.instant("reject", cat="sched",
                               args={"reason": "queue_full",
                                     "depth": self.sched.queue_depth})
                raise RejectedError(
                    "queue_full", f"queue depth {self.sched.queue_depth} at "
                    f"cap {cfg.max_queue_depth}")
            victims.append(extra)
        for v in victims:
            # the victim's terminal "request" span carries the
            # shed_overload reason; this instant names who displaced it
            if tr.enabled:
                tr.instant("displace", cat="sched",
                           args={"victim": v.rid, "priority": priority})
            self.sched.cancel(v, "shed_overload")
            self.metrics.requests_shed += 1
        if deadline_s is None:
            deadline_s = cfg.default_deadline_s
        deadline = None if deadline_s is None \
            else time.perf_counter() + float(deadline_s)
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_token_id=eos_token_id, priority=priority,
                      deadline=deadline,
                      block_hashes=prompt_hashes or [])
        if not self.sched.has_work():
            # traffic resuming after a drain (or first ever): re-anchor the
            # throughput window so tokens/sec reflects the current serving
            # rate instead of decaying across idle gaps
            self.metrics.on_traffic_resume()
        self.sched.submit(req)
        self._requests[req.rid] = req
        self.metrics.requests_submitted += 1
        if tr.enabled:
            tr.instant("submit", cat="sched",
                       args={"rid": req.rid, "prompt_tokens": len(prompt),
                             "queue_depth": self.sched.queue_depth,
                             "priority": priority})
        return req.rid

    def try_submit(self, prompt_ids, max_new_tokens: int = 16,
                   eos_token_id: Optional[int] = None,
                   deadline_s: Optional[float] = None,
                   priority: int = 0) -> Optional[str]:
        """Backpressure-friendly submit: None instead of RejectedError when
        admission control sheds the request (malformed requests still raise
        ValueError — those are caller bugs, not load)."""
        try:
            return self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                               eos_token_id=eos_token_id,
                               deadline_s=deadline_s, priority=priority)
        except RejectedError:
            return None

    def cancel(self, rid: str, reason: str = "cancelled") -> bool:
        """Cancel a request in ANY live state: queued requests leave the
        queue, running ones release slot + pages the same call. Returns
        False when the request already reached a terminal state (cancel is
        then a no-op — its outcome stands)."""
        req = self._requests[rid]
        if req.done:
            return False
        slot = req.slot
        self.sched.cancel(req, reason)
        if slot is not None:
            self._clear_slot_arrays(slot)
        self.metrics.requests_cancelled += 1
        return True

    def begin_drain(self) -> None:
        """Stop admitting (submits now raise ``RejectedError("draining")``)
        and shed everything still queued, WITHOUT stepping: the fleet
        router drains one replica while the rest absorb — residents here
        keep stepping in the normal drive loop until they run dry, and
        the shed requests re-enter the router's fleet queue. (The
        single-engine path is :meth:`drain`, which also steps to
        completion.) ``resume_admission()`` reopens the engine."""
        self._draining = True
        for req in list(self.sched.queue):
            self.sched.cancel(req, "drained")
            self.metrics.requests_shed += 1

    def drain(self, max_steps: Optional[int] = None) -> Dict[str, "RequestOutput"]:
        """Graceful shutdown: stop admitting, shed everything still
        queued (:meth:`begin_drain`), and step until every resident
        finishes. Returns all retained outputs. ``resume_admission()``
        reopens the engine."""
        self.begin_drain()
        steps = 0
        # has_work(), not "slots occupied": a resident preempted-and-
        # requeued mid-drain sits in the QUEUE between steps and must still
        # be driven to a terminal state
        while self.sched.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return {rid: self.poll(rid) for rid in self._requests}

    def resume_admission(self) -> None:
        """Reopen admission after :meth:`drain`."""
        self._draining = False

    def set_brownout(self, on: Optional[bool]) -> None:
        """Force brownout on/off; ``None`` returns to automatic
        (occupancy-triggered via ``brownout_occupancy``)."""
        self._brownout_forced = on

    @property
    def brownout(self) -> bool:
        if self._brownout_forced is not None:
            return self._brownout_forced
        thr = self.config.brownout_occupancy
        return thr is not None and self.block_pool.occupancy() >= thr

    def request(self, rid: str) -> Request:
        """The LIVE request record (read-only by contract). The fleet
        router's per-step done/state probe — :meth:`poll` copies the
        prompt and token lists, which is the wrong cost for a scan over
        every in-flight request every router tick."""
        return self._requests[rid]

    def live_rids(self, state: Optional[RequestState] = None) -> List[str]:
        """Rids of retained requests that are NOT yet terminal,
        optionally narrowed to one live state — the fleet layer's
        kill/drain enumeration (the public seam; reaching into the
        retention dict is not part of the contract)."""
        out: List[str] = []
        for rid, req in list(self._requests.items()):
            if state is None:
                if not req.done:
                    out.append(rid)
            elif req.state is state:
                out.append(rid)
        return out

    def poll(self, rid: str) -> RequestOutput:
        """Non-blocking status + tokens-so-far for a request."""
        req = self._requests[rid]
        return RequestOutput(rid=req.rid, state=req.state.value,
                             prompt=list(req.prompt), tokens=list(req.tokens),
                             finish_reason=req.finish_reason,
                             ttft_s=req.ttft, preemptions=req.preemptions)

    def stream(self, rid: str) -> Iterator[int]:
        """Yield a request's tokens as they are produced, driving the
        engine's step loop while the request is unfinished."""
        req = self._requests[rid]
        sent = 0
        while True:
            while sent < len(req.tokens):
                yield req.tokens[sent]
                sent += 1
            if req.done:
                return
            self.step()

    def run(self, max_steps: Optional[int] = None) -> Dict[str, RequestOutput]:
        """Drain everything submitted so far; returns all retained outputs
        (see :meth:`forget` for releasing finished requests on a
        long-lived server)."""
        steps = 0
        while self.sched.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return {rid: self.poll(rid) for rid in self._requests}

    def forget(self, rid: str) -> RequestOutput:
        """Release a request's retained state (a daemon serving unbounded
        traffic calls this after consuming the output — nothing is pruned
        automatically, so poll() keeps working until then). A request still
        live (queued, preempted-requeued, or mid-decode) is cancelled
        first, so its slot and pages always return to the pool. Returns the
        final output."""
        req = self._requests[rid]
        if not req.done:
            self.cancel(rid, "forgotten")
        out = self.poll(rid)
        del self._requests[rid]
        return out

    def has_work(self) -> bool:
        return self.sched.has_work()

    # -- SLO attribution ------------------------------------------------

    def _judge_slo(self, req: Request) -> str:
        """One verdict per terminal request (metrics.SLO_VERDICTS):

        - ``shed``      — cancelled (caller cancel, load shed, drain,
                          displacement): the engine chose not to serve it;
        - ``failed``    — engine-side failure (watchdog, quarantine,
                          prefill error, pool exhaustion);
        - ``ttft_miss`` — finished past the TTFT SLO, or timed out before
                          producing a first token;
        - ``tpot_miss`` — finished with mean inter-token latency past the
                          TPOT SLO, or timed out mid-decode;
        - ``good``      — finished inside both budgets (trivially, when
                          no SLO is configured).
        """
        cfg = self.config
        if req.state is RequestState.CANCELLED:
            return "shed"
        if req.state is RequestState.FAILED:
            return "failed"
        if req.state is RequestState.TIMEOUT:
            # a deadline blown before the first token is a TTFT story; one
            # blown mid-decode is a decode-rate story
            return "ttft_miss" if req.first_token_time is None \
                else "tpot_miss"
        # FINISHED: judge against the configured budgets
        if cfg.ttft_slo_s is not None and req.ttft is not None \
                and req.ttft > cfg.ttft_slo_s:
            return "ttft_miss"
        if cfg.tpot_slo_s is not None and len(req.tokens) > 1 \
                and req.first_token_time is not None \
                and req.finish_time is not None:
            tpot = (req.finish_time - req.first_token_time) \
                / (len(req.tokens) - 1)
            if tpot > cfg.tpot_slo_s:
                return "tpot_miss"
        return "good"

    def _slo_on_terminal(self, req: Request) -> None:
        verdict = self._judge_slo(req)
        req.slo_verdict = verdict
        self.metrics.note_slo(
            verdict,
            goodput_tokens=len(req.tokens) if verdict == "good" else 0)

    # -- control-plane probes (monitor/export.py serves these) ----------

    def health(self) -> "tuple[bool, Dict[str, Any]]":
        """Liveness: can this engine make progress RIGHT NOW? False while
        a watchdog-abandoned step is still wedged in device compute (the
        engine is alive but every step skips the device — exactly the
        state a router should route around). Detail carries incident
        recency (last watchdog trip / quarantine age) for dashboards."""
        now = time.perf_counter()
        # snapshot before use: this runs on the admin server's probe
        # thread while the engine thread may clear _wedged between the
        # None check and the is_alive() call (AttributeError -> a 500
        # from the very probe that promises 200-or-503)
        w = self._wedged
        wedged = w is not None and w.is_alive()
        detail: Dict[str, Any] = {
            "wedged": wedged,
            "steps": self.metrics.steps,
            "watchdog_trips": self.metrics.watchdog_trips,
            "logit_quarantines": self.metrics.logit_quarantines,
            "last_watchdog_trip_age_s": None if self._last_trip_time is None
            else round(now - self._last_trip_time, 3),
            "last_quarantine_age_s": None
            if self._last_quarantine_time is None
            else round(now - self._last_quarantine_time, 3),
        }
        return (not wedged), detail

    def readiness(self) -> "tuple[bool, Dict[str, Any]]":
        """Readiness: should a router send NEW traffic here? Requires
        admission open (not draining), KV headroom above the brownout
        line, and the resident serving program compiled (a cold replica
        answering ready would eat the fleet's tail latency with its first
        compile). Detail names every failing bit."""
        reasons = []
        if self._draining:
            reasons.append("draining")
        if self.brownout:
            reasons.append("brownout")
        warm = self._mixed_warm if self._mixed else self._decode_warm
        if not warm:
            reasons.append("cold")
        detail: Dict[str, Any] = {
            "reasons": reasons,
            "queue_depth": self.sched.queue_depth,
            "kv_blocks_free": self.block_pool.num_blocks
            - self.block_pool.used_count,
            "kv_occupancy": round(self.block_pool.occupancy(), 4),
            "resident_compiled": warm,
        }
        return (not reasons), detail

    # -- tracing / post-mortem -----------------------------------------

    def _flight(self, trigger: str, **detail) -> None:
        """Flight-recorder dump (no-op unless ``trace_dir`` armed one)."""
        if self.flight is not None:
            self.flight.record(trigger, detail)

    def dump_trace(self, path: Optional[str] = None) -> str:
        """Write the trace ring as Chrome-trace/Perfetto JSON. Default
        path: ``<trace_dir>/trace_serving_<stamp>.json``."""
        if path is None:
            if not self.config.trace_dir:
                raise ValueError("dump_trace() needs a path when "
                                 "ServingConfig.trace_dir is unset")
            path = os.path.join(
                self.config.trace_dir,
                f"trace_serving_{time.strftime('%Y%m%d-%H%M%S')}"
                f"_{dump_seq():04d}_{os.getpid()}.json")
        return self.tracer.dump(path)

    @property
    def prefill_chunk_tokens(self) -> int:
        """EFFECTIVE prefill chunk length (unified: the budget-packing
        granularity; legacy: the resident chunked-prefill shape, 0 =
        monolithic prefill). May differ from the config field: when the
        field is 0 the engine derives ``4 * block_size`` (unified always,
        legacy only with ``prefix_cache``) without mutating the caller's
        config."""
        return self._chunk

    @property
    def mixed_step_tokens(self) -> int:
        """Packed token capacity of the ONE resident mixed step (0 on the
        legacy two-program engine)."""
        return self._mixed_tokens if self._mixed else 0

    # ------------------------------------------------------------------
    # one scheduler step
    # ------------------------------------------------------------------

    def step(self) -> None:
        """Admit + prefill new requests, then run ONE ragged decode step
        over every active slot — bounded by deadlines, the step watchdog
        and the logit guard, so one pathological request or one wedged
        step never takes the engine down.

        Spans (``monitor/tracing.py``; ``ds.<name>`` on the profiler's
        clock, docs/observability.md): ``step`` holds ``expire``,
        ``promote``, ``admit``, then for the unified engine ``plan``,
        ``pack``, ``dispatch`` (inside ``compile`` when the call carries
        one), ``fetch``, ``harvest`` and ``bookkeeping``; each carries the
        step's number."""
        with self.tracer.span("step", cat="engine", step=self._step_no):
            self._step()

    def _step(self) -> None:
        # chaos-drill point: DS_FAULT=stall:tag=serving_step wedges the
        # worker here; a bounded stall must leave the queue drainable
        fault_injection.maybe_stall("stall", tag="serving_step",
                                    step=self._step_no,
                                    stream=self.fault_stream)
        # re-pin THIS engine's mesh before any lazy program build: model
        # code (QuantDense tp_reduce, mixtral expert gating) consults the
        # process-global mesh at trace time, and another engine
        # constructed since may have replaced it
        from ...parallel.topology import set_mesh

        set_mesh(self.engine.mesh)
        t0 = time.perf_counter()

        # 1. deadline sweep: queued requests past deadline are shed at the
        # gate; running ones end terminal TIMEOUT, pages back to the pool
        tr, step_no = self.tracer, self._step_no
        with tr.span("expire", cat="host", args={"step": step_no}):
            now = time.perf_counter()
            self.sched.expire_queued(now)
            for slot, req in list(self.sched.active()):
                if req.state is RequestState.RUNNING and req.expired(now):
                    self.sched.timeout(req, "deadline")
                    self._clear_slot_arrays(slot)
                    self.metrics.requests_timeout += 1
        # 1b. wedged-backend gate, BEFORE any device dispatch: while the
        # previously-abandoned (watchdog-tripped) step is still stuck in
        # device compute, neither prefill nor decode may touch the backend
        # — an unguarded prefill against a hung device would wedge the
        # main thread, the very failure the watchdog exists to survive.
        # Host-side work above (deadline shedding) still ran; the sleep
        # keeps drive loops from spinning.
        if self._wedged is not None:
            if self._wedged.is_alive():
                self.metrics.watchdog_skips += 1
                tr.instant("watchdog_skip", cat="engine",
                           args={"step": step_no})
                time.sleep(min(0.05, self.config.step_watchdog_s))
                self._account_reaped()
                # no record_step: a skipped step's sleep in the latency
                # distribution would read as HEALTHY p50 mid-outage;
                # watchdog_skips is the signal for this condition
                self._finish_step_bookkeeping(t0, self.brownout,
                                              record_latency=False)
                return
            self._wedged = None

        # 1c. fold landed host-tier promotions into the pool BEFORE
        # admission and grant planning: a transfer that arrived since
        # the last step unblocks its request's grants this very step
        self._pump_promotions()

        # 2. FIFO admission (interleaved with the running batch: admitted
        # requests join this very step's decode, or — chunked — start
        # consuming the step's prefill token budget); brownout caps each
        # admission's remaining token budget
        brownout = self.brownout
        with tr.span("admit", cat="host", args={"step": step_no}) as admit:
            admitted = 0
            while True:
                req = self.sched.admit_next()
                if req is None:
                    break
                admitted += 1
                if brownout:
                    capped = len(req.tokens) + self.config.brownout_max_new_tokens
                    if capped < req.max_new_tokens:
                        req.max_new_tokens = capped
                        self.metrics.brownout_admissions += 1
                if req.prefix_len:
                    # prefix-cache hit: these tokens are SERVED without being
                    # recomputed (their pages were acquired, not refilled —
                    # host-tier hits stream up instead of recomputing)
                    self.metrics.prefix_hits += 1
                    self.metrics.cached_prefill_tokens += req.prefix_len
                    self.metrics.prefill_tokens += req.prefix_len
                if self.host_tier is not None:
                    if req.host_prefix_len:
                        self.metrics.kv_host_hits += 1
                        self.metrics.kv_host_hit_tokens += req.host_prefix_len
                    else:
                        self.metrics.kv_host_misses += 1
                if req.host_hits:
                    # host-matched pages: start their async device_put NOW so
                    # the transfers overlap everything the packed step does;
                    # the request's own suffix grants wait only on the fold
                    self._schedule_promotions(req)
                if self._mixed:
                    # unified path: the request's table row is live from
                    # admission (no sentinel rows — its packed segments carry
                    # their own query_len, so an un-granted row is inert) and
                    # its prompt starts consuming the packed step's budget
                    self._write_table_row(req)
                    continue
                if self._chunk:
                    continue  # prefill runs below, under the step token budget
                try:
                    self._prefill(req)
                except BlockPoolError:
                    raise  # accounting invariant broken — never swallow
                except Exception as e:
                    self._fail_prefill(req, e)
            self._account_reaped()
            admit.set(admitted=admitted, queue_depth=self.sched.queue_depth)
        # second pump: a promotion scheduled by THIS step's admission may
        # already be ready — folding it here lets the request take its
        # first suffix grant in the same step. When promotion folds are
        # the ONLY way anyone can make progress (every resident is
        # promotion-blocked, nothing else would pack), blocking on the
        # transfer is free — the packed step had nothing to do — so the
        # fold waits instead of burning an empty step of TTFT
        self._pump_promotions(wait=self._promotions_only())

        if self._mixed:
            # the whole device half of the step is ONE packed dispatch
            self._step_mixed(t0, brownout)
            return

        if self._skip_step_if_wedged(t0, brownout):
            return

        # 2b. the prefill half of the LEGACY step: at most
        # ``prefill_token_budget`` prompt tokens run through the resident
        # chunked-prefill program, round-robin across prefilling residents,
        # so the decode below still fires every iteration — a long prompt
        # can no longer head-of-line-block resident decoders
        if self._chunk:
            self._run_prefill_chunks()

        # 3. page growth for this step's appends, preempting when dry
        # (mid-prefill residents own every prompt page already and do not
        # decode this step — nothing to grow)
        self._grow_decode_pages()

        # 4. the single ragged decode step over all slots, watchdog-bounded
        active = [(s, r) for s, r in self.sched.active()
                  if r.state is RequestState.RUNNING and not r.prefilling]
        w = self._wedged  # snapshot (the _wedged read-once discipline)
        if active and w is not None and w.is_alive():
            # a prefill chunk tripped the watchdog THIS step: nothing else
            # may touch the backend until the abandoned call clears (the
            # step-top gate only covers trips from earlier steps)
            self.metrics.watchdog_skips += 1
            active = []
        if active:
            if self._decode_fn is None:
                self._decode_fn = self._build_decode()
            self._rng, rng = jax.random.split(self._rng)
            corrupt = np.zeros((self.config.max_batch_size,), bool)
            spec = fault_injection.maybe_flag("corrupt_logits",
                                              tag="serving_step",
                                              step=self._step_no,
                                              stream=self.fault_stream)
            if spec is not None:
                # NaN ONE slot's logits (spec may pin slot=N); the guard
                # must quarantine that request, not the batch. A pin that
                # is malformed, out of range, or names an empty slot falls
                # back to the first active slot — an injection point must
                # never crash the serving loop it is drilling
                active_slots = {s for s, _ in active}
                try:
                    pin = int(spec.params["slot"])
                except (KeyError, ValueError):
                    pin = active[0][0]
                if pin not in active_slots:
                    pin = active[0][0]
                corrupt[pin] = True
            step_no = self._step_no
            # snapshot everything the guarded thread touches on THIS thread:
            # after a watchdog trip the main loop moves on, and the
            # abandoned thread must not read engine state mid-mutation
            pool = self.pool
            tables = jnp.asarray(self._tables)
            seq_lens = jnp.asarray(self._seq_lens)
            last_tok = jnp.asarray(self._last_tok)
            corrupt_j = jnp.asarray(corrupt)

            def device_step():
                # chaos point INSIDE the guarded region: a slow/wedged
                # step is exactly what the watchdog exists for
                fault_injection.maybe_stall("slow_step", tag="serving_step",
                                            step=step_no,
                                            stream=self.fault_stream)
                return self._decode_dispatch(pool, tables, seq_lens,
                                             last_tok, corrupt_j, rng)

            t_dec = time.perf_counter()
            was_warm = self._decode_warm
            try:
                # heartbeat.py's first-beat rule, in-process: the first
                # decode invocation contains the XLA compile (often far
                # beyond any sane step budget) and is never watchdog-judged;
                # steady-state wedges — the r5 outage class — always are
                with self._compile_span(not was_warm, "decode"), tr.span(
                        "dispatch", cat="engine", ring="decode_step",
                        args={"step": step_no, "program": "decode",
                              "active": len(active)}):
                    if was_warm:
                        toks, bad, self.pool = self._guarded(device_step)
                    else:
                        toks, bad, self.pool = device_step()
                        self._decode_warm = True
            except StepWatchdogTimeout as e:
                log_dist(f"serving: step watchdog tripped: {e}", ranks=[0])
                self.metrics.watchdog_trips += 1
                self._last_trip_time = time.perf_counter()
                rids = [r.rid for _, r in active]
                if tr.enabled:
                    tr.instant("watchdog_trip", cat="engine",
                               args={"step": step_no, "rids": rids})
                for slot, req in active:
                    self.sched.fail(req, "step_watchdog")
                    self._clear_slot_arrays(slot)
                    self.metrics.requests_failed += 1
                # post-mortem: the last trace events + metrics, naming the
                # requests the trip failed
                self._flight("watchdog_trip", step=step_no, rids=rids,
                             budget_s=self.config.step_watchdog_s)
            else:
                if was_warm:
                    # first-beat rule for gauges too: the compile-carrying
                    # call's wall time would report a garbage MFU/MBU
                    self._note_decode_perf(time.perf_counter() - t_dec,
                                           tokens=len(active))
                with tr.span("fetch", cat="host", args={"step": step_no}):
                    toks = np.asarray(toks)
                    bad = np.asarray(bad)
                for slot, req in active:
                    if self.config.logit_guard and bad[slot]:
                        self._quarantine(slot, req, step_no, where="decode")
                        continue
                    req.seq_len += 1
                    self._seq_lens[slot] = req.seq_len
                    # a generated token may have just FILLED a page —
                    # content-index it so identical continuations
                    # (multi-turn replays) can reuse it
                    self._commit_full_blocks(req)
                    self._harvest(req, int(toks[slot]))

        # 5. bookkeeping
        self._finish_step_bookkeeping(t0, brownout)

    def _finish_step_bookkeeping(self, t0: float, brownout: bool,
                                 record_latency: bool = True) -> None:
        with self.tracer.span("bookkeeping", cat="host",
                              args={"step": self._step_no}):
            self._bookkeeping(t0, brownout, record_latency)

    def _bookkeeping(self, t0: float, brownout: bool,
                     record_latency: bool) -> None:
        self._step_no += 1
        m = self.metrics
        m.steps += 1
        if record_latency:
            m.record_step(time.perf_counter() - t0)
        m.queue_depth = self.sched.queue_depth
        m.active_seqs = len(self.sched.active())
        m.blocks_used = self.block_pool.used_count
        m.blocks_cached = self.block_pool.cached_count
        m.prefix_evictions = self.block_pool.evictions
        prefilling = [r for _, r in self.sched.active() if r.prefilling]
        m.prefill_waiting = len(prefilling)
        m.prefill_queue_age_s = 0.0 if not prefilling else \
            time.perf_counter() - min(r.submit_time for r in prefilling)
        m.brownout_active = brownout
        if self.host_tier is not None:
            m.kv_pages_demoted = self.block_pool.demotions
            m.kv_host_blocks = len(self.host_tier)
            m.kv_host_bytes = self.host_tier.bytes
            m.promote_queue_depth = len(self._promote_q)
        m.recompiles = self.perf.recompile_total
        # HBM watermarks: one capability probe, then free on CPU; on TPU
        # the live/peak bytes ride every snapshot and flight dump
        m.hbm_bytes_in_use, m.hbm_peak_bytes = self.perf.memory_watermarks()
        if self.monitor is not None and self.config.monitor_every and \
                self._step_no % self.config.monitor_every == 0:
            self.monitor.write_events(m.to_events(self._step_no))

    # ------------------------------------------------------------------
    # the unified mixed step (ONE resident program per step)
    # ------------------------------------------------------------------

    def _grow_decode_pages(self, spec_plan: Optional[Dict[str, List[int]]]
                           = None) -> None:
        """Guarantee every decoding resident pages for the tokens this
        step appends — one for a plain decode row, ``1 + k`` positions
        for a verify row carrying ``k`` drafts — preempting (lowest
        priority, newest first) when the pool runs dry; shared append
        targets are copied-on-write. Draft pages degrade FIRST: when the
        pool cannot grow a resident's speculative lookahead, its drafts
        are dropped (plain decode this step) before anyone is evicted —
        speculation must never convert verify appetite into
        preemptions."""
        bs = self.block_pool.block_size
        for _, req in list(self.sched.active()):
            if req.state is not RequestState.RUNNING or req.prefilling:
                continue  # preempted below while growing an earlier slot
            k = len(spec_plan.get(req.rid, ())) if spec_plan else 0
            if k and not self.sched.ensure_decode_headroom(req, lookahead=k):
                spec_plan.pop(req.rid, None)
                k = 0
                # pages the partial lookahead growth may have allocated
                # are returned right away (the rollback helper keeps
                # exactly the next append's page)
                self._drop_trailing_pages(req)
            while not self.sched.ensure_decode_headroom(req):
                victim = self.sched.preempt_victim(exclude=req)
                if victim is None:
                    # nobody left to evict: the pool cannot hold even one
                    # sequence at this length — a sizing error, not traffic
                    slot = req.slot
                    self.sched.fail(req, "kv_pool_exhausted")
                    self._clear_slot_arrays(slot)
                    self.metrics.requests_failed += 1
                    break
                self._preempt(victim)
            else:
                # this step appends at seq_len .. seq_len + k: never into
                # a page other sequences still reference — copy-on-write
                # every spanned page first
                for idx in range(req.seq_len // bs,
                                 (req.seq_len + k) // bs + 1):
                    self._ensure_exclusive(req, idx)
                self._write_table_row(req)  # growth may have added a page
                continue
            break

    def _plan_speculation(self, grants: Dict[str, int]
                          ) -> Dict[str, List[int]]:
        """Draft tokens per decoding resident (``{rid: drafts}``) for
        this step's verify rows, sized to the packed step's LEFTOVER
        capacity: every decode row's guaranteed token and every prefill
        grant are reserved first, so speculation degrades to k=0 plain
        decode under prefill pressure instead of starving admissions.
        The per-request adaptive cap (``req.spec_k``: grown on full
        accepts, halved on full rejects) keeps adversarial traffic from
        paying verify tokens for drafts that never land; a drafter with
        nothing to propose skips the row entirely."""
        if self._drafter is None:
            return {}
        cfg = self.config
        decoders = [r for _, r in self.sched.active()
                    if r.state is RequestState.RUNNING and not r.prefilling]
        plan: Dict[str, List[int]] = {}
        if not decoders:
            return plan
        slack = self._mixed_tokens - len(decoders) - sum(grants.values())
        for req in decoders:  # slot-ascending (the packing order)
            if slack <= 0:
                break
            if req.spec_k < 0:
                req.spec_k = cfg.spec_tokens
            # a verify row may commit up to k + 1 tokens and appends KV
            # through position seq_len + k: cap by the remaining token
            # budget and the sequence length cap as well as the packed
            # slack and — where dispatch width costs (see __init__) —
            # the adaptive per-request cap
            cap = req.spec_k if self._spec_adaptive else cfg.spec_tokens
            k = min(cap, slack, req.remaining_new - 1,
                    cfg.max_model_len - 1 - req.seq_len)
            if k <= 0:
                continue
            drafts = self._drafter.draft(req.resume_tokens, k)
            if not drafts:
                continue
            drafts = [int(t) for t in drafts[:k]]
            plan[req.rid] = drafts
            slack -= len(drafts)
        return plan

    def _drop_trailing_pages(self, req: Request) -> int:
        """Free every pool page past the one the NEXT append targets —
        the page-drop half of speculative rollback. Pages holding only
        rejected draft KV were never content-indexed (hashes commit from
        the ACCEPTED ``seq_len`` watermark only), so freeing them blanks
        them; the partially-rejected page at ``seq_len // bs`` is kept
        and simply overwritten by the next append."""
        keep = req.seq_len // self.block_pool.block_size + 1
        if len(req.blocks) <= keep:
            return 0
        drop = req.blocks[keep:]
        del req.blocks[keep:]
        self.block_pool.free(drop, req.rid)
        self._write_table_row(req)
        self.metrics.spec_pages_dropped += len(drop)
        return len(drop)

    def _commit_verify_row(self, slot: int, req: Request,
                           drafts: List[int], preds: List[int]) -> int:
        """Greedy accept-prefix over one verify row: ``preds[j]`` is the
        target model's prediction AFTER the row's j-th packed token, so
        draft ``j`` is accepted iff every earlier draft was and
        ``preds[j] == drafts[j]``. Commits the accepted drafts plus the
        model's own bonus token, rewinds ``seq_len`` past exactly the
        accepted KV (rejected appends beyond it become invisible and are
        overwritten later), drops whole rejected pages, and adapts the
        request's draft cap. Returns the number of committed tokens."""
        k = len(drafts)
        a = 0
        while a < k and drafts[a] == preds[a]:
            a += 1
        commit = drafts[:a] + [preds[a]]
        # an accepted EOS ends the stream exactly where the plain engine
        # would have stopped generating — nothing after it commits
        if req.eos_token_id is not None and req.eos_token_id in commit:
            commit = commit[:commit.index(req.eos_token_id) + 1]
        commit = commit[:req.remaining_new]
        m = self.metrics
        m.spec_drafted += k
        m.spec_accepted += a
        m.spec_committed += len(commit)
        m.spec_verify_rows += 1
        # decay-then-add: the request-local counters track the RECENT
        # accept rate (horizon of a few verifies), not lifetime — the
        # gate below must release as soon as the stream turns
        # predictable, not after new accepts outvote an old cold streak
        req.spec_drafted = req.spec_drafted * 0.75 + k
        req.spec_accepted = req.spec_accepted * 0.75 + a
        # adaptive cap (AIMD on the observed accept length): a
        # fully-confirmed draft DOUBLES the cap — a stream that just
        # turned predictable (the post-divergence loop regime) must not
        # crawl back one token per step — while any miss shrinks the cap
        # to just past what actually landed (floor 1 so the request
        # keeps probing and can recover). Without the shrink, a stream
        # accepting 2 of 12 every step would pay 13-token verify rows
        # forever to commit 3 — the adversarial overhead this cap exists
        # to bound
        if a == k:
            req.spec_k = min(self.config.spec_tokens, max(req.spec_k * 2, 2))
        else:
            req.spec_k = max(1, min(req.spec_k, a + 1))
        # chronic-miss gate on top of the per-step AIMD: a request whose
        # RECENT accept rate (the decayed counters above) stays under
        # 1/3 — judged only once enough recent drafts exist — is clamped
        # to a 1-token probe. The AIMD alone oscillates on streams that
        # loop briefly then break (grow on the loop, collapse on the
        # break), paying wide verify rows for ~nothing; the probe keeps
        # the request cheap AND keeps sampling, and a few accepted
        # probes dominate the decayed window, so the gate releases
        # within steps of the stream turning predictable
        if req.spec_drafted >= 8 and \
                req.spec_accepted * 3 < req.spec_drafted:
            req.spec_k = 1
        # KV bookkeeping: the row appended positions seq_len .. seq_len+k
        # (the last committed token's own KV is in the pool only when the
        # commit ends on a draft; a commit ending on the bonus token
        # leaves it to the next step's append — both land on
        # seq_len = len(resume_tokens) - 1, the plain-decode invariant)
        req.seq_len += len(commit)
        self._seq_lens[slot] = req.seq_len
        self._drop_trailing_pages(req)
        # every committed token flows through the ONE harvest path (eos /
        # length finish, TTFT, stream, metrics). EOS and the length cap
        # can only trigger on the LAST committed token by construction
        # (the truncations above), so the hash commit between the two
        # harvest phases always runs on a live, page-owning request
        for t in commit[:-1]:
            self._harvest(req, t)
        self._commit_full_blocks(req)
        self._harvest(req, commit[-1])
        return len(commit)

    def _step_mixed(self, t0: float, brownout: bool) -> None:
        """The device half of the unified step: pack one decode token per
        running resident (``k + 1`` for a speculating one — its drafts
        ride the same row as a prefill-like verify segment) plus this
        step's budgeted prefill chunks into a single ragged token batch,
        dispatch the ONE resident program, and harvest per row.
        Raggedness — segment offsets/lengths, chunk starts, context
        lengths, block tables — rides as DATA, so any traffic mix reuses
        one compile and one dispatch."""
        cfg = self.config
        if self._skip_step_if_wedged(t0, brownout):
            return
        tr, step_no = self.tracer, self._step_no
        preempted0 = self.metrics.preemptions

        with tr.span("plan", cat="host", args={"step": step_no}) as plan:
            # prefill grants: round-robin chunk-sized shares of the step's
            # token budget across mid-prefill residents (admission order);
            # grants to one request are contiguous, so several rounds simply
            # extend its packed segment
            grants = self.sched.plan_prefill_grants(self._chunk_budget,
                                                    self._chunk)
            # speculation over what the grants left, then page growth sized
            # to each row's appends (drafts dropped before anyone is evicted)
            spec_plan = self._plan_speculation(grants)
            self._grow_decode_pages(spec_plan)
            # RE-plan grants: growth may have preempted a grantee, and its
            # share must redistribute to the surviving prefillers instead of
            # being silently wasted this step. The re-planned total can only
            # shrink or redistribute (bounded by the same budget and a
            # smaller owed set), so the packed capacity the speculation plan
            # was sized against still holds
            grants = self.sched.plan_prefill_grants(self._chunk_budget,
                                                    self._chunk)
            for _, req in list(self.sched.active()):
                if not req.prefilling or req.rid not in grants:
                    continue
                try:
                    # chaos point: DS_FAULT=flaky_prefill fails ITS request
                    # host-side, before it is packed — everyone else still
                    # rides this step
                    fault_injection.maybe_fail("flaky_prefill",
                                               exc=RuntimeError,
                                               tag="serving_prefill",
                                               step=self._step_no,
                                               stream=self.fault_stream)
                except Exception as e:
                    grants.pop(req.rid, None)
                    self._fail_prefill(req, e)
                    continue
                # COW any chunk-spanned page another sequence still references
                # (appends into shared pages must be impossible by
                # construction, not by luck)
                start, n = req.prefill_done, grants[req.rid]
                bs = self.block_pool.block_size
                for idx in range(start // bs, (start + n - 1) // bs + 1):
                    self._ensure_exclusive(req, idx)
                self._write_table_row(req)
            plan.set(grants=len(grants),
                     preempted=self.metrics.preemptions - preempted0)

        with tr.span("pack", cat="host", args={"step": step_no}):
            # pack segments slot-ascending (the ragged kernel's contract) —
            # decode rows are 1 token (1 + k for a speculating row: the last
            # committed token plus its drafts, a prefill-like verify segment
            # starting at seq_len), granted prefill rows up to their grant,
            # everything else (empty slots, un-granted prefillers) is inert
            R, T = cfg.max_batch_size, self._mixed_tokens
            ids = np.zeros((1, T), np.int32)
            pos = np.full((1, T), -1, np.int32)
            trow = np.full((1, T), -1, np.int32)
            row_start = np.zeros((R,), np.int32)
            row_len = np.zeros((R,), np.int32)
            row_cs = np.zeros((R,), np.int32)
            row_cl = np.zeros((R,), np.int32)
            decodes, prefills = [], []
            cursor = 0
            for slot, req in self.sched.active():
                if req.state is not RequestState.RUNNING:
                    continue
                if req.prefilling:
                    n = grants.get(req.rid, 0)
                    if not n:
                        continue
                    start = req.prefill_done
                    ids[0, cursor:cursor + n] = \
                        req.resume_tokens[start:start + n]
                    pos[0, cursor:cursor + n] = np.arange(start, start + n)
                    trow[0, cursor:cursor + n] = slot
                    row_start[slot], row_len[slot] = cursor, n
                    row_cs[slot], row_cl[slot] = start, start + n
                    prefills.append((slot, req, n,
                                     start + n >= req.prefill_target))
                    cursor += n
                else:
                    drafts = spec_plan.get(req.rid) or []
                    n = 1 + len(drafts)
                    ids[0, cursor] = self._last_tok[slot]
                    if drafts:
                        ids[0, cursor + 1:cursor + n] = drafts
                    pos[0, cursor:cursor + n] = \
                        np.arange(req.seq_len, req.seq_len + n)
                    trow[0, cursor:cursor + n] = slot
                    row_start[slot], row_len[slot] = cursor, n
                    row_cs[slot], row_cl[slot] = req.seq_len, req.seq_len + n
                    decodes.append((slot, req, drafts))
                    cursor += n
            assert cursor <= T, f"packed {cursor} tokens into a {T}-token step"
            if cursor == 0:
                self._finish_step_bookkeeping(t0, brownout)
                return

            # corrupt_logits chaos, both tags, as DATA (no recompile): the
            # serving_step vocabulary pins a decode slot (slot=N, falling back
            # to the first decode row on a bad/absent pin), serving_prefill
            # flags the first packed chunk. Each tag is probed only when a
            # matching row is packed — a bounded (fails=N) spec must spend its
            # budget on a step it can actually poison
            corrupt = np.zeros((R,), bool)
            if decodes:
                fspec = fault_injection.maybe_flag("corrupt_logits",
                                                   tag="serving_step",
                                                   step=self._step_no,
                                                   stream=self.fault_stream)
                if fspec is not None:
                    decode_slots = {s for s, _, _ in decodes}
                    try:
                        pin = int(fspec.params["slot"])
                    except (KeyError, ValueError):
                        pin = decodes[0][0]
                    if pin not in decode_slots:
                        pin = decodes[0][0]
                    corrupt[pin] = True
            if prefills and fault_injection.maybe_flag(
                    "corrupt_logits", tag="serving_prefill",
                    step=self._step_no,
                    stream=self.fault_stream) is not None:
                corrupt[prefills[0][0]] = True

            # packed width: the full capacity, or — with mixed_step_buckets —
            # the narrowest compiled bucket that fits this step's packed
            # tokens (decode-only steps stop paying the full padded batch)
            W = T
            if self._bucket_widths is not None:
                W = next(w for w in self._bucket_widths if w >= cursor)

            self._rng, rng = jax.random.split(self._rng)
            # snapshot everything the guarded thread touches on THIS thread
            # (the watchdog-abandonment rule of the legacy decode step)
            call_args = (self.engine.params, self.pool,
                         jnp.asarray(self._tables),
                         jnp.asarray(ids[:, :W]), jnp.asarray(trow[:, :W]),
                         jnp.asarray(pos[:, :W]),
                         jnp.asarray(row_start), jnp.asarray(row_len),
                         jnp.asarray(row_cs), jnp.asarray(row_cl),
                         jnp.asarray(corrupt), rng)

        has_prefill = bool(prefills)

        def device_step():
            # chaos points INSIDE the guarded region: the decode and
            # prefill stall vocabularies both land on the one dispatch
            # now. slow_chunk is probed only when prefill rows are packed
            # — a bounded spec must spend its budget on a step that
            # exercises prefill work (same rule as the corrupt probes)
            fault_injection.maybe_stall("slow_step", tag="serving_step",
                                        step=step_no,
                                        stream=self.fault_stream)
            if has_prefill:
                fault_injection.maybe_stall("slow_chunk",
                                            tag="serving_prefill",
                                            step=step_no,
                                            stream=self.fault_stream)
            return self._mixed_dispatch(call_args, W)

        n_decode_packed = sum(1 + len(d) for _, _, d in decodes)
        n_prefill = cursor - n_decode_packed
        n_drafted = n_decode_packed - len(decodes)
        t_dev = time.perf_counter()
        # first-beat rule per WIDTH: each bucket's first call carries its
        # own XLA compile and is never watchdog-judged; steady-state
        # wedges always are
        was_warm = W in self._warm_widths
        try:
            # the one engine span of the unified step, carrying the
            # per-row decode/prefill/verify token split (what
            # decode_step + chunked_prefill used to say in two spans)
            with self._compile_span(not was_warm, self._mixed_name(W)), \
                    tr.span("dispatch", cat="engine", ring="mixed_step",
                            args={"step": step_no,
                                  "decode_tokens": len(decodes),
                                  "verify_tokens": n_drafted,
                                  "prefill_tokens": n_prefill,
                                  "width": W,
                                  "rows": len(decodes) + len(prefills),
                                  "context_tokens": int(row_cl.sum())}):
                if was_warm:
                    toks, bad, self.pool = self._guarded(device_step)
                else:
                    toks, bad, self.pool = device_step()
                    self._warm_widths.add(W)
                    self._mixed_warm = True
        except StepWatchdogTimeout as e:
            log_dist(f"serving: step watchdog tripped: {e}", ranks=[0])
            self.metrics.watchdog_trips += 1
            self._last_trip_time = time.perf_counter()
            packed = [(s, r) for s, r, _ in decodes] + \
                     [(s, r) for s, r, _, _ in prefills]
            rids = [r.rid for _, r in packed]
            if tr.enabled:
                tr.instant("watchdog_trip", cat="engine",
                           args={"step": step_no, "rids": rids})
            for slot, req in packed:
                self.sched.fail(req, "step_watchdog")
                self._clear_slot_arrays(slot)
                self.metrics.requests_failed += 1
            self._flight("watchdog_trip", step=step_no, rids=rids,
                         budget_s=cfg.step_watchdog_s)
        else:
            t_end = time.perf_counter()
            # the host's wait on the device: the tokens come back here
            with tr.span("fetch", cat="host", args={"step": step_no}):
                toks = np.asarray(toks)
                bad = np.asarray(bad)
            with tr.span("harvest", cat="host",
                         args={"step": step_no}) as harvest:
                committed = 0
                for slot, req, n, final in prefills:
                    start = req.prefill_done
                    req.prefill_done = start + n
                    req.seq_len = start + n
                    self.metrics.prefill_tokens += n
                    self.metrics.prefill_tokens_computed += n
                    self.metrics.window_tokens += n
                    committed += n
                    # guard EVERY chunk and BEFORE content-indexing: poisoned
                    # KV must never park on the prefix-cache LRU
                    if cfg.logit_guard and bad[slot]:
                        self._quarantine(slot, req, step_no, where="prefill")
                        continue
                    self._commit_full_blocks(req)
                    if final:
                        # last chunk: token one (TTFT ends here) — the row's
                        # LAST packed position; the slot decodes next step
                        self._seq_lens[slot] = req.seq_len
                        self._harvest(
                            req,
                            int(toks[row_start[slot] + row_len[slot] - 1]))
                        committed += 1
                had_verify = False
                for slot, req, drafts in decodes:
                    if cfg.logit_guard and bad[slot]:
                        # one poisoned position anywhere in the row (drafts
                        # included) fails ITS request; nothing from the row
                        # commits, so poisoned KV can neither be harvested
                        # nor content-indexed
                        self._quarantine(slot, req, step_no, where="decode")
                        continue
                    if drafts:
                        # verify row: greedy accept-prefix over the row's
                        # k + 1 predictions, rollback past the accepted KV
                        preds = [int(toks[row_start[slot] + j])
                                 for j in range(len(drafts) + 1)]
                        committed += self._commit_verify_row(slot, req,
                                                             drafts, preds)
                        had_verify = True
                        continue
                    req.seq_len += 1
                    self._seq_lens[slot] = req.seq_len
                    # a generated token may have just FILLED a page —
                    # content-index it so identical continuations hit
                    self._commit_full_blocks(req)
                    self._harvest(req, int(toks[row_start[slot]]))
                    committed += 1
                if had_verify:
                    self.metrics.spec_steps += 1
                harvest.set(committed=committed)
            if was_warm:
                # first-beat rule for gauges too (compile wall time would
                # report garbage utilization). Tokens = what the step
                # COMMITTED (prefill progress + decode commits): rejected
                # draft positions are real FLOPs but not throughput —
                # they are the overhead speculation pays, reported via
                # spec_drafted/spec_accepted, never folded into tokens/sec
                self._note_mixed_perf(t_end - t_dev, tokens=committed,
                                      width=W)

        self._finish_step_bookkeeping(t0, brownout)

    def _mixed_name(self, width: int) -> str:
        """Perf-registry name of the resident mixed program at ``width``
        — ONE name by default (the one-compile invariant's key), one per
        bucket with ``mixed_step_buckets`` (each bucket is its own
        resident program with its own fingerprint, so dispatching across
        buckets never reads as a recompile)."""
        return "mixed_step" if self._bucket_widths is None \
            else f"mixed_step[{width}]"

    def _mixed_dispatch(self, call_args, width: Optional[int] = None):
        """The ONE observed entry to the resident mixed program (per
        packed width when bucketing). Every dispatch is
        fingerprint-observed first (shapes/dtypes/statics): a fingerprint
        change IS a recompile, so the sentinel fires a `recompile` tracer
        event + registry counter naming the offending argument before the
        stall even happens. The first call also captures the program's
        cost model for MFU/MBU."""
        if width is None:
            width = self._mixed_tokens
        name = self._mixed_name(width)
        fn = self._mixed_fns.get(width)
        if fn is None:
            fn = self._mixed_fns[width] = self._build_mixed_step(width)
        (params, pool, tables, ids, token_rows, append_pos, row_start,
         row_len, chunk_start, context_len, corrupt, rng) = call_args
        self.perf.observe_call(
            name,
            params=self.perf.cached_spec("params", params),
            pool=pool, tables=tables, ids=ids, token_rows=token_rows,
            append_pos=append_pos, row_start=row_start, row_len=row_len,
            chunk_start=chunk_start, context_len=context_len,
            corrupt=corrupt, rng=rng)
        out = fn(*call_args)
        if self.perf.programs.program(name).cost_pending:
            # first call (watchdog-exempt): lowering is cached by jax, so
            # this pays no second trace and no XLA compile
            self.perf.capture_cost(
                name, fn, call_args,
                fallback=lambda: self._mixed_cost_estimate(width))
        return out

    def _quarantine(self, slot: int, req: Request, step_no: int,
                    where: str) -> None:
        """NaN/Inf logits on one packed row: quarantine THAT request
        (terminal FAILED, pages returned, flight dump), never the batch."""
        if self.tracer.enabled:
            self.tracer.instant("quarantine", cat="engine",
                                args={"rid": req.rid, "slot": slot,
                                      "step": step_no, "where": where})
        self.sched.fail(req, "corrupt_logits")
        self._clear_slot_arrays(slot)
        self.metrics.logit_quarantines += 1
        self._last_quarantine_time = time.perf_counter()
        self.metrics.requests_failed += 1
        self._flight("logit_quarantine", rid=req.rid, slot=slot,
                     step=step_no, where=where)

    def _note_mixed_perf(self, dt_s: float, tokens: int,
                         width: Optional[int] = None) -> None:
        """Per-step utilization of the unified program (serving snapshot +
        flight dumps): MBU stays the honest gauge — the step is still
        dominated by the param + KV read."""
        name = self._mixed_name(width if width is not None
                                else self._mixed_tokens)
        vals = self.perf.on_program_step(name, dt_s, tokens=tokens)
        m = self.metrics
        m.mixed_flops_per_step = vals["flops_per_step"]
        m.mixed_bytes_per_step = vals["bytes_per_step"]
        m.mixed_mfu = vals["mfu"]
        m.mixed_mbu = vals["mbu"]
        m.mixed_tokens_per_sec_per_chip = vals["tokens_per_sec_per_chip"]

    def _mixed_cost_estimate(self, width: Optional[int] = None):
        """Hand-rolled mixed-step cost where the backend has no cost
        model: the packed batch computes every padded token position and
        reads params once + every row's table-width KV walk — exactly the
        compiled program's work."""
        mcfg = getattr(self.engine.module, "config", None)
        if mcfg is None:
            return None
        B, ctx = self.config.max_batch_size, self.config.max_model_len
        return {
            "flops": (width if width is not None else self._mixed_tokens)
            * transformer_flops_per_token(mcfg, ctx),
            "bytes_accessed": estimate_decode_step_bytes(
                mcfg, B, ctx, param_bytes(self.engine.params),
                kv_bytes_per_elem=self._kv_bytes_per_elem),
        }

    # ------------------------------------------------------------------
    # defrag
    # ------------------------------------------------------------------

    def defrag(self) -> int:
        """Compact allocated pages to the low end of the pool (one gather
        per pool array) and rewrite the live block tables. Returns the
        number of pages that moved."""
        mapping, src = self.block_pool.defrag_plan()
        moved = sum(1 for old, new in mapping.items() if old != new)
        # in-flight promotions target pages by id: remap them with the
        # block tables, or the pump would drop them as stale and strand
        # their requests promotion-blocked forever
        for e in list(self._promote_q):
            e.dst_bids = [mapping[b] for b in e.dst_bids]
        if moved:
            if self._defrag_fn is None:
                def _gather(pool, src_ids):
                    # pool arrays carry a leading layer axis: [L, N, ...]
                    return jax.tree_util.tree_map(
                        lambda a: jnp.take(a, src_ids, axis=1), pool)

                r = self.engine._replicated
                self._defrag_fn = jax.jit(_gather,
                                          donate_argnums=self._donate and (0,),
                                          in_shardings=(r, r),
                                          out_shardings=r)
            self.pool = self._defrag_fn(self.pool, jnp.asarray(src, jnp.int32))
        for _, req in self.sched.active():
            req.blocks = [mapping[b] for b in req.blocks]
            if self._mixed or not req.prefilling:
                # unified path: every resident's table row is live (its
                # packed segments carry their own lengths, so nothing can
                # append where it should not). LEGACY: mid-prefill
                # residents keep a SENTINEL decode row until their last
                # chunk lands (writing it early would let the decode step
                # append garbage into their pages)
                self._write_table_row(req)
        return moved

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _account_reaped(self) -> None:
        """Count the requests the scheduler shed at the admission gate
        (deadline-expired while queued) this step."""
        if self.sched.reaped:
            self.metrics.requests_timeout += len(self.sched.reaped)
            self.sched.reaped.clear()

    def _compile_span(self, compiling: bool, program: str):
        """A ``compile`` span around a dispatch known to carry an XLA
        compile (a program's or width's first call), so a trace can put
        the idle device down to compiling; nothing otherwise."""
        if not compiling:
            return contextlib.nullcontext()
        return self.tracer.span("compile", cat="host",
                                args={"step": self._step_no,
                                      "program": program})

    def _skip_step_if_wedged(self, t0: float, brownout: bool) -> bool:
        """A watchdog trip EARLIER in this very step (a wedged promotion
        fold) leaves the backend hung: skip the device half entirely —
        the step-top gate only covers trips from PREVIOUS steps. Shared
        by the mixed dispatch and the legacy path; True = caller
        returns (bookkeeping already finished, latency unrecorded)."""
        w = self._wedged
        if w is None or not w.is_alive():
            return False
        self.metrics.watchdog_skips += 1
        self._finish_step_bookkeeping(t0, brownout, record_latency=False)
        return True

    # -- tiered KV: async host->device promotion ------------------------

    def _promotions_only(self) -> bool:
        """True when promotion folds are the ONLY path to progress this
        step: promotions are in flight and every running resident is a
        promotion-blocked prefiller (no decoder, no grantable chunk).
        Blocking on the transfer is then free — the packed step would
        have dispatched nothing — and saves the blocked request a whole
        step of TTFT. With ANY other runnable work this returns False
        and the packed step never waits on a transfer."""
        if not self._promote_q:
            return False
        for _, r in self.sched.active():
            if r.state is not RequestState.RUNNING:
                continue
            if not r.prefilling or not r.promote_pending:
                return False
        return True

    def _schedule_promotions(self, req: Request) -> None:
        """Start the async host->device transfer of every host-tier page
        admission matched for ``req``: ``jax.device_put`` returns
        immediately (the DMA overlaps whatever the engine does next) and
        the entry joins the promotion queue; :meth:`_pump_promotions`
        folds it into the pool once the transfer lands. The host entry
        itself is consumed only when the page's hash COMMITS into the
        device index (after the logit guard passed the first suffix
        chunk), so a corrupted or abandoned promotion never destroys the
        clean host copy."""
        hits, req.host_hits = req.host_hits, []
        if not hits:
            return
        # chaos point: DS_FAULT=corrupt_promote:tag=serving_tier poisons
        # ONE promoted page's payload in transit (float leaves -> NaN).
        # The existing logit-guard path must quarantine the request on
        # its first suffix chunk BEFORE the page's hash is re-indexed —
        # poisoned KV must never enter either tier's content index
        corrupt = fault_injection.maybe_flag(
            "corrupt_promote", tag="serving_tier",
            step=self._step_no,
            stream=self.fault_stream) is not None
        payloads = [p for _, _, p in hits]
        if corrupt:
            # payload leaves are host numpy copies by construction
            # (kv_tiers.fetch_paged_block) — no device sync here
            payloads[0] = jax.tree_util.tree_map(
                lambda a: np.full_like(a, np.nan)
                if np.issubdtype(a.dtype, np.floating) else a, payloads[0])
        # ONE transfer for the whole matched prefix, padded to a pow2
        # page width by repeating the last page (duplicate scatter
        # targets carrying identical content are deterministic), so the
        # fold program compiles once per width — a bounded set
        k = len(hits)
        width = next_pow2(k)
        payloads += [payloads[-1]] * (width - k)
        payload = jax.tree_util.tree_map(
            lambda *ls: np.concatenate(ls, axis=1), *payloads)
        arr = jax.device_put(payload, self.engine._replicated)
        idxs = [i for i, _, _ in hits]
        self._promote_q.append(_Promotion(
            req=req, block_idxs=idxs,
            dst_bids=[req.blocks[i] for i in idxs],
            arr=arr, width=width,
            admit_order=req.admit_order, t_sched=time.perf_counter()))
        if self.tracer.enabled:
            self.tracer.instant("kv_promote_start", cat="pool",
                                args={"rid": req.rid, "pages": k})
        if self.config.sync_promote:
            # the A/B control: block on the transfer and fold at
            # admission — promotion latency lands squarely in TTFT
            self._pump_promotions(wait=True)

    def _pump_promotions(self, wait: bool = False) -> None:
        """Fold every LANDED promotion into the device pool (one
        fixed-shape scatter per page — compiled once, tier residency
        rides as data). Entries whose request left its admission segment
        (preempted / terminal) are dropped — their target pages are back
        in the pool and may already belong to someone else; the host
        entries they would have consumed survive for the retry. A
        not-yet-landed transfer stays queued and blocks only its own
        request's next grant (the scheduler's ``promote_pending`` gate);
        the packed step never waits. ``wait=True`` (sync_promote A/B)
        folds everything immediately. The fold is watchdog-bounded like
        every other device call (``DS_FAULT=slow_promote`` drills it)."""
        w = self._wedged
        if w is not None and w.is_alive():
            return  # backend wedged: queued transfers wait it out
        q, self._promote_q = self._promote_q, []
        if not q:
            return
        with self.tracer.span("promote", cat="host",
                              args={"step": self._step_no,
                                    "queued": len(q)}):
            self._fold_promotions(q, wait)

    def _fold_promotions(self, q: List[Any], wait: bool) -> None:
        m = self.metrics
        tr = self.tracer
        still: List[Any] = []
        for i, e in enumerate(q):
            req = e.req
            if not (req.state is RequestState.RUNNING
                    and req.admit_order == e.admit_order
                    and req.promote_pending > 0
                    and all(idx < len(req.blocks)
                            and req.blocks[idx] == bid
                            for idx, bid in zip(e.block_idxs, e.dst_bids))):
                m.kv_promote_cancelled += len(e.block_idxs)
                if tr.enabled:
                    tr.instant("kv_promote_cancel", cat="pool",
                               args={"rid": req.rid,
                                     "pages": len(e.block_idxs)})
                if req.state is RequestState.RUNNING and \
                        req.admit_order == e.admit_order:
                    # the request still EXPECTS this promotion but the
                    # target pages no longer line up (nothing should
                    # reach here — defrag remaps the queue — but a
                    # promotion-blocked request with no promotion coming
                    # would hold its slot forever): preempt-requeue it,
                    # so re-admission re-matches both tiers cleanly
                    self._preempt(req)
                continue
            if not wait and not _tree_ready(e.arr):
                still.append(e)
                continue
            pool = self.pool  # snapshot for the guarded thread
            # dst padded like the payload: the repeated tail pages write
            # their own content again (idempotent)
            dst_ids = e.dst_bids + [e.dst_bids[-1]] * (e.width
                                                       - len(e.dst_bids))
            dst = jnp.asarray(dst_ids, jnp.int32)
            step_no = self._step_no
            fn = self._insert_fns.get(e.width)
            if fn is None:
                from .kv_tiers import insert_paged_block

                r = self.engine._replicated
                fn = self._insert_fns[e.width] = jax.jit(
                    insert_paged_block,
                    donate_argnums=self._donate and (0,),
                    in_shardings=(r, r, r), out_shardings=r)

            def device_fold():
                # chaos point INSIDE the guarded region: a slow/wedged
                # promotion is bounded by the step watchdog exactly like
                # a wedged decode step
                fault_injection.maybe_stall("slow_promote",
                                            tag="serving_tier",
                                            step=step_no,
                                            stream=self.fault_stream)
                return fn(pool, dst, e.arr)

            try:
                if e.width in self._promote_warm:
                    self.pool = self._guarded(device_fold)
                else:
                    self.pool = device_fold()
                    self._promote_warm.add(e.width)
            except StepWatchdogTimeout as exc:
                log_dist(f"serving: promotion watchdog tripped for "
                         f"{req.rid}: {exc}", ranks=[0])
                m.watchdog_trips += 1
                self._last_trip_time = time.perf_counter()
                if tr.enabled:
                    tr.instant("watchdog_trip", cat="engine",
                               args={"step": step_no, "rids": [req.rid],
                                     "where": "kv_promote"})
                slot = req.slot
                self.sched.fail(req, "step_watchdog")
                self._clear_slot_arrays(slot)
                m.requests_failed += 1
                self._flight("watchdog_trip", step=step_no,
                             rids=[req.rid], where="kv_promote",
                             budget_s=self.config.step_watchdog_s)
                # backend wedged: nothing else may touch the device —
                # requeue the rest (the step-top gate takes over)
                still.extend(q[i + 1:])
                break
            req.promote_pending -= len(e.block_idxs)
            m.kv_pages_promoted += len(e.block_idxs)
            now = time.perf_counter()
            m.promote_hist.observe(now - e.t_sched)
            if tr.enabled:
                tr.complete("kv_promote", e.t_sched, now, cat="pool",
                            args={"rid": req.rid,
                                  "pages": len(e.block_idxs)})
        self._promote_q.extend(still)

    def _guarded(self, fn):
        """Run the device step under the wall-clock watchdog (the
        staleness-judgment pattern of ``elasticity/heartbeat.py``, applied
        in-process): past ``step_watchdog_s`` the step is abandoned and
        :class:`StepWatchdogTimeout` raised — the caller fails the step's
        requests and keeps serving. Abandoned results are simply discarded:
        the watchdog forces donation OFF (see ``__init__``), so pool
        updates are functional and dropping one is always safe. The worker
        thread only reads snapshots taken by the caller, never live engine
        state."""
        timeout = self.config.step_watchdog_s
        if not timeout or timeout <= 0:
            return fn()
        box: Dict[str, Any] = {}

        def run():
            try:
                box["out"] = fn()
            except BaseException as e:  # surfaced on the caller thread
                box["err"] = e

        t = threading.Thread(target=run, daemon=True,
                             name="serving-step-watchdog")
        t.start()
        t.join(timeout)
        # a step that lands between the join timeout and these checks is
        # kept — barely-late work beats a spurious failure
        if "err" in box:
            raise box["err"]
        if "out" in box:
            return box["out"]
        self._wedged = t  # step() skips the device while this is alive
        raise StepWatchdogTimeout(
            f"resident serving step exceeded {timeout:.3f}s wall-clock "
            f"(step {self._step_no})")

    # -- performance accounting ----------------------------------------

    def _decode_dispatch(self, pool, tables, seq_lens, last_tok, corrupt,
                         rng):
        """The ONE entry to the resident decode program. Every dispatch is
        fingerprint-observed first (shapes/dtypes/statics): a fingerprint
        change IS a recompile, so the sentinel fires a `recompile` tracer
        event + registry counter naming the offending argument before the
        stall even happens. The first successful call also captures the
        program's cost model (FLOPs / bytes-accessed) for MFU/MBU."""
        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        args = (self.engine.params, pool, tables, seq_lens, last_tok,
                corrupt, rng)
        self.perf.observe_call(
            "decode",
            params=self.perf.cached_spec("params", self.engine.params),
            pool=pool, tables=tables, seq_lens=seq_lens, last_tok=last_tok,
            corrupt=corrupt, rng=rng)
        out = self._decode_fn(*args)
        if self.perf.programs.program("decode").cost_pending:
            # first call (watchdog-exempt): lowering is cached by jax, so
            # this pays no second trace and no XLA compile
            self.perf.capture_cost("decode", self._decode_fn, args,
                                   fallback=self._decode_cost_estimate)
        return out

    def _decode_cost_estimate(self):
        """Hand-rolled decode-step cost where the backend has no cost
        model: every slot computes against the full padded table width —
        exactly the work the compiled program does."""
        mcfg = getattr(self.engine.module, "config", None)
        if mcfg is None:
            return None
        B, ctx = self.config.max_batch_size, self.config.max_model_len
        return {
            "flops": estimate_decode_step_flops(mcfg, B, ctx),
            "bytes_accessed": estimate_decode_step_bytes(
                mcfg, B, ctx, param_bytes(self.engine.params),
                kv_bytes_per_elem=self._kv_bytes_per_elem),
        }

    def _note_decode_perf(self, dt_s: float, tokens: int) -> None:
        """Per-step utilization: decode is bandwidth-bound, so MBU +
        tokens/sec/chip are the honest gauges (MFU included for
        completeness); values land in the serving snapshot and every
        flight dump."""
        vals = self.perf.on_program_step("decode", dt_s, tokens=tokens)
        m = self.metrics
        m.decode_flops_per_step = vals["flops_per_step"]
        m.decode_bytes_per_step = vals["bytes_per_step"]
        m.decode_mfu = vals["mfu"]
        m.decode_mbu = vals["mbu"]
        m.decode_tokens_per_sec_per_chip = vals["tokens_per_sec_per_chip"]

    def perf_summary(self) -> Dict[str, Any]:
        """Performance-accounting block for CLI reports and bench
        artifacts: device peaks, HBM watermarks, the compiled-program
        table (fingerprints, compile/recompile counts, cost-model FLOPs)
        and the latest utilization values."""
        out = self.perf.summary()
        out["compile_counts"] = dict(self.compile_counts)
        return out

    @property
    def mixed_step_widths(self) -> List[int]:
        """Packed widths the mixed step may dispatch at: the full
        capacity alone by default, the bounded bucket set with
        ``mixed_step_buckets`` (``compile_counts["mixed_step"]`` is
        bounded by its length)."""
        if not self._mixed:
            return []
        return list(self._bucket_widths) if self._bucket_widths is not None \
            else [self._mixed_tokens]

    def speculation_status(self) -> Dict[str, Any]:
        """Speculative-decoding status for CLI reports (``ds_serve``
        final report, ``ds_report`` next to the compiled-program table):
        drafter kind, configured cap, and the rolling acceptance
        numbers. ``enabled`` False when speculation is off."""
        m = self.metrics
        return {
            "enabled": self._drafter is not None,
            "drafter": self._drafter.kind if self._drafter is not None
            else None,
            "spec_tokens": self.config.spec_tokens,
            "drafted": m.spec_drafted,
            "accepted": m.spec_accepted,
            "accept_rate": round(m.spec_accept_rate, 4),
            "tokens_per_verify": round(m.spec_tokens_per_verify, 4),
            "pages_dropped": m.spec_pages_dropped,
        }

    def tier_status(self) -> Dict[str, Any]:
        """Tier-table block for CLI reports (``ds_serve`` final report,
        ``ds_report``, /statusz): per-tier capacity/occupancy plus the
        movement counters and promotion latency percentiles. ``enabled``
        False without a host tier."""
        if self.host_tier is None:
            return {"enabled": False}
        m = self.metrics
        hist = m.promote_hist
        return {
            "enabled": True,
            "tiers": [
                {"tier": "device", "capacity_blocks": self.config.num_blocks,
                 "blocks": self.block_pool.used_count
                 + self.block_pool.cached_count,
                 "indexed_blocks": self.block_pool.indexed_count,
                 "evictions": self.block_pool.evictions,
                 "demotions": self.block_pool.demotions},
                self.host_tier.stats(),
            ],
            "host_hits": m.kv_host_hits,
            "host_misses": m.kv_host_misses,
            "host_hit_tokens": m.kv_host_hit_tokens,
            "host_hit_rate": round(m.host_hit_rate, 4),
            "pages_promoted": m.kv_pages_promoted,
            "promote_cancelled": m.kv_promote_cancelled,
            "promote_queue_depth": len(self._promote_q),
            "promote_wait_p50_s": hist.percentile(0.5)
            if hist.count else None,
            "promote_wait_p95_s": hist.percentile(0.95)
            if hist.count else None,
        }

    def quant_status(self) -> Dict[str, Any]:
        """Quantized-serving block for CLI reports (``ds_serve`` final
        report, ``ds_report``, /statusz): weight mode + byte shift +
        worst-leaf reconstruction error (the load-time accounting from
        ``inference/quant.py``), and whether the TP collectives ride
        int8 payloads. ``enabled`` False when both modes are off."""
        icfg = self.engine.config
        qw = getattr(icfg, "quantize_weights", None)
        qc = bool(getattr(icfg, "quantized_collectives", False))
        out: Dict[str, Any] = {
            "enabled": bool(qw or qc),
            "weights": qw,
            "collectives": qc,
            "mp_size": self.engine.mp_world_size,
        }
        if qc:
            out["psum_block"] = getattr(icfg, "quantized_psum_block", 256)
        summary = getattr(self.engine, "quant_summary", None)
        if summary:
            out.update(summary)
        return out

    def _write_table_row(self, req: Request) -> None:
        row = np.full((self.nb_max,), self.block_pool.sentinel, np.int32)
        row[:len(req.blocks)] = req.blocks
        self._tables[req.slot] = row

    def _clear_slot_arrays(self, req_or_slot) -> None:
        slot = req_or_slot if isinstance(req_or_slot, int) else \
            req_or_slot.slot
        if slot is None:
            return
        self._tables[slot] = self.block_pool.sentinel
        self._seq_lens[slot] = 0
        self._last_tok[slot] = 0

    def _fail_prefill(self, req: Request, e: Exception) -> None:
        """A failing prefill (flaky_prefill chaos, OOM on one pathological
        prompt, ...) fails ITS request; the engine keeps serving everyone
        else."""
        log_dist(f"serving: prefill failed for {req.rid}: "
                 f"{type(e).__name__}: {e}", ranks=[0])
        slot = req.slot
        self.sched.fail(req, f"prefill_error:{type(e).__name__}")
        self._clear_slot_arrays(slot)
        self.metrics.requests_failed += 1

    def _prefill(self, req: Request) -> None:
        """Run the admitted request's (resume-)prompt through the bucketed
        prefill program: appends its KV into its pages, samples token one.
        NaN/Inf logits quarantine the request (terminal FAILED, pages
        returned) instead of poisoning its stream. LEGACY (monolithic)
        path — requires a from-empty sequence, so it never runs when the
        prefix cache may hand the request a cached prefix."""
        # chaos point: DS_FAULT=flaky_prefill raises here; step() fails the
        # request and keeps serving
        fault_injection.maybe_fail("flaky_prefill", exc=RuntimeError,
                                   tag="serving_prefill", step=self._step_no,
                                   stream=self.fault_stream)
        tokens = req.resume_tokens
        L = len(tokens)
        Tb = next_pow2(max(L, self.config.prefill_bucket_min))
        pf_name = f"prefill[{Tb}]"
        with self.tracer.span("dispatch", cat="engine", ring="prefill",
                              args={"step": self._step_no,
                                    "program": pf_name, "rid": req.rid,
                                    "tokens": L, "bucket": Tb}):
            self._write_table_row(req)
            ids = np.zeros((1, Tb), np.int32)
            ids[0, :L] = tokens
            fn = self._prefill_fns.get(Tb)
            if fn is None:
                fn = self._prefill_fns[Tb] = self._build_prefill(Tb)
            self._rng, rng = jax.random.split(self._rng)
            pf_args = (self.engine.params, self.pool,
                       jnp.asarray(self._tables[req.slot][None]),
                       jnp.asarray(ids), jnp.asarray([L], np.int32), rng)
            self.perf.observe_call(
                pf_name,
                params=self.perf.cached_spec("params", self.engine.params),
                pool=pf_args[1], table_row=pf_args[2], ids=pf_args[3],
                length=pf_args[4], rng=rng)
            tok, bad, self.pool = fn(*pf_args)
            if self.perf.programs.program(pf_name).cost_pending:
                self.perf.capture_cost(pf_name, fn, pf_args)
        req.seq_len = L
        req.prefill_done = L
        self._seq_lens[req.slot] = L
        self.metrics.prefill_tokens += L
        self.metrics.prefill_tokens_computed += L
        self.metrics.window_tokens += L
        if self.config.logit_guard and bool(np.asarray(bad)[0]):
            self._quarantine(req.slot, req, self._step_no, where="prefill")
            return
        self._harvest(req, int(np.asarray(tok)[0]))

    # -- chunked prefill (the prefill half of the mixed step) -----------

    def _run_prefill_chunks(self) -> None:
        """Spend this step's prefill token budget: round-robin one chunk at
        a time across mid-prefill residents (admission order) until the
        budget is gone or nobody is owed prefill. Decode always runs after
        — the budget is what bounds prefill's share of the step."""
        budget = self._chunk_budget
        while budget > 0:
            # promotion-blocked residents are skipped (their next chunk
            # would attend host pages still in flight) — same rule as
            # the unified step's grant planner
            pending = sorted((r for _, r in self.sched.active()
                              if r.prefilling and not r.promote_pending),
                             key=lambda r: r.admit_order)
            if not pending:
                return
            progressed = False
            for req in pending:
                if budget <= 0:
                    return
                n = min(self._chunk, budget,
                        req.prefill_target - req.prefill_done)
                if n <= 0:
                    continue
                try:
                    self._prefill_chunk(req, n)
                except BlockPoolError:
                    raise  # accounting invariant broken — never swallow
                except StepWatchdogTimeout as e:
                    # the chunk wedged on-device: fail ITS request with
                    # watchdog semantics and stop dispatching this step —
                    # the wedged-backend gate keeps later steps off the
                    # device until the abandoned call clears
                    log_dist(f"serving: chunked prefill watchdog tripped "
                             f"for {req.rid}: {e}", ranks=[0])
                    self.metrics.watchdog_trips += 1
                    self._last_trip_time = time.perf_counter()
                    if self.tracer.enabled:
                        self.tracer.instant(
                            "watchdog_trip", cat="engine",
                            args={"step": self._step_no, "rids": [req.rid],
                                  "where": "chunked_prefill"})
                    slot = req.slot
                    self.sched.fail(req, "step_watchdog")
                    self._clear_slot_arrays(slot)
                    self.metrics.requests_failed += 1
                    self._flight("watchdog_trip", step=self._step_no,
                                 rids=[req.rid], where="chunked_prefill",
                                 budget_s=self.config.step_watchdog_s)
                    return
                except Exception as e:
                    self._fail_prefill(req, e)
                    continue
                budget -= n
                progressed = True
            if not progressed:
                return

    def _prefill_chunk(self, req: Request, n: int) -> None:
        """Run ``n`` prompt tokens (<= the compiled chunk length) through
        the resident chunked-prefill program. Chunk offset, valid length,
        block table and cached-prefix length all ride as DATA — every call
        reuses the one compile. The final chunk samples token one (TTFT)
        and activates the slot for decode."""
        fault_injection.maybe_fail("flaky_prefill", exc=RuntimeError,
                                   tag="serving_prefill", step=self._step_no,
                                   stream=self.fault_stream)
        # chaos point: NaN this chunk's logits as DATA (no recompile) — the
        # guard must quarantine the request BEFORE its pages are
        # content-indexed, or the poison would be served to the next
        # identical prompt
        corrupt = fault_injection.maybe_flag(
            "corrupt_logits", tag="serving_prefill",
            step=self._step_no,
            stream=self.fault_stream) is not None
        tokens = req.resume_tokens
        start = req.prefill_done
        bs = self.block_pool.block_size
        # COW any target page another sequence still references (reachable
        # only through unusual sharing patterns — prefix matches are block-
        # aligned — but appends into shared pages must be impossible by
        # construction, not by luck)
        for idx in range(start // bs, (start + n - 1) // bs + 1):
            self._ensure_exclusive(req, idx)
        row = np.full((1, self.nb_max), self.block_pool.sentinel, np.int32)
        row[0, :len(req.blocks)] = req.blocks
        ids = np.zeros((1, self._chunk), np.int32)
        ids[0, :n] = tokens[start:start + n]
        if self._chunked_prefill_fn is None:
            self._chunked_prefill_fn = self._build_chunked_prefill()
        self._rng, rng = jax.random.split(self._rng)
        pool = self.pool  # snapshot for the guarded thread (decode rule)
        row_j, ids_j = jnp.asarray(row), jnp.asarray(ids)
        start_j = jnp.asarray([start], np.int32)
        len_j = jnp.asarray([n], np.int32)
        corrupt_j = jnp.asarray([corrupt])

        step_no = self._step_no
        call_args = (self.engine.params, pool, row_j, ids_j, start_j,
                     len_j, corrupt_j, rng)
        # recompile sentinel: the chunked-prefill program is the mixed
        # step's OTHER resident compile — a fingerprint change here is
        # the same class of alarm as one on decode
        self.perf.observe_call(
            "chunked_prefill",
            params=self.perf.cached_spec("params", self.engine.params),
            pool=pool, table_row=row_j, ids=ids_j, start=start_j,
            length=len_j, corrupt=corrupt_j, rng=rng)

        def device_call():
            # chaos point INSIDE the guarded region (the slow_step analog
            # for the mixed step's prefill half)
            fault_injection.maybe_stall("slow_chunk", tag="serving_prefill",
                                        step=step_no,
                                        stream=self.fault_stream)
            return self._chunked_prefill_fn(*call_args)

        # chunked prefill is the mixed step's OTHER device program, so the
        # step watchdog bounds it exactly like decode (a wedged chunk must
        # fail ITS request and keep the engine serving, not hang every
        # tenant); the first call carries the XLA compile and is exempt
        t_ck = time.perf_counter()
        with self._compile_span(not self._chunked_warm, "chunked_prefill"), \
                self.tracer.span("dispatch", cat="engine",
                                 ring="prefill_chunk",
                                 args={"step": self._step_no,
                                       "program": "chunked_prefill",
                                       "rid": req.rid, "start": start,
                                       "tokens": n}):
            if self._chunked_warm:
                tok, bad, self.pool = self._guarded(device_call)
                # warm calls only: the compile-carrying first chunk's wall
                # time would report a garbage utilization (first-beat rule)
                self.perf.on_program_step("chunked_prefill",
                                          time.perf_counter() - t_ck,
                                          tokens=n)
            else:
                tok, bad, self.pool = device_call()
                self._chunked_warm = True
                mcfg = getattr(self.engine.module, "config", None)
                self.perf.capture_cost(
                    "chunked_prefill", self._chunked_prefill_fn, call_args,
                    fallback=None if mcfg is None else lambda: {
                        "flops": self._chunk * transformer_flops_per_token(
                            mcfg, self.config.max_model_len)})
        req.prefill_done = start + n
        req.seq_len = start + n
        self.metrics.prefill_tokens += n
        self.metrics.prefill_tokens_computed += n
        self.metrics.window_tokens += n
        # guard EVERY chunk (the chunk's last position attends everything
        # before it, so NaN KV anywhere upstream surfaces here) and guard
        # BEFORE content-indexing: a quarantined request's pages must
        # blank on release, never park on the LRU where the next
        # identical prompt would reuse the poisoned KV
        if self.config.logit_guard and bool(np.asarray(bad)[0]):
            self._quarantine(req.slot, req, self._step_no,
                             where="prefill_chunk")
            return
        self._commit_full_blocks(req)
        if req.prefill_done < req.prefill_target:
            return  # mid-prompt: no token sampled, slot stays decode-idle
        # last chunk: activate the slot for the ragged decode step
        self._write_table_row(req)
        self._seq_lens[req.slot] = req.seq_len
        self._harvest(req, int(np.asarray(tok)[0]))

    def _ensure_exclusive(self, req: Request, block_idx: int) -> None:
        """Copy-on-write guard for append paths: the page at ``block_idx``
        of the request's table must be referenced ONLY by this request
        before anything scatters into it. Shared pages are forked
        (``BlockPool.cow``) and device-copied; the table is rewritten."""
        if block_idx >= len(req.blocks):
            return  # page not allocated yet (growth allocates exclusively)
        bid = req.blocks[block_idx]
        if not self.block_pool.is_shared(bid):
            return
        new = self.block_pool.cow(bid, req.rid)
        if self._copy_blocks_fn is None:
            from ...models.layers import copy_paged_blocks

            r = self.engine._replicated
            self._copy_blocks_fn = jax.jit(
                copy_paged_blocks, donate_argnums=self._donate and (0,),
                in_shardings=(r, r, r), out_shardings=r)
        self.pool = self._copy_blocks_fn(self.pool,
                                         jnp.asarray([bid], jnp.int32),
                                         jnp.asarray([new], jnp.int32))
        req.blocks[block_idx] = new
        self.metrics.cow_copies += 1
        if self.tracer.enabled:
            self.tracer.instant("cow", cat="pool",
                                args={"rid": req.rid, "src": bid,
                                      "dst": new})

    def _commit_full_blocks(self, req: Request) -> None:
        """Content-index every COMPLETELY written page of this sequence
        (hash chained over the prefix) so later identical prompts reuse it.
        Cheap and idempotent: already-indexed pages return immediately."""
        if not self.config.prefix_cache:
            return
        bs = self.block_pool.block_size
        full = req.seq_len // bs
        tokens = None
        while len(req.block_hashes) < full:
            # generated tokens filled pages past the admission-time hashes
            j = len(req.block_hashes)
            if tokens is None:
                tokens = req.resume_tokens
            prev = req.block_hashes[j - 1] if j else None
            req.block_hashes.append(self.block_pool.canonical_key(
                chain_hash(prev, tokens[j * bs:(j + 1) * bs])))
        for idx in range(req.committed_blocks, full):
            self.block_pool.commit_hash(req.blocks[idx],
                                        req.block_hashes[idx])
        req.committed_blocks = max(req.committed_blocks, full)

    def _harvest(self, req: Request, token: int) -> None:
        """Account one sampled token; recycle the slot the step a sequence
        finishes (EOS or token budget)."""
        req.tokens.append(token)
        self._last_tok[req.slot] = token
        self.metrics.tokens_generated += 1
        self.metrics.window_tokens += 1
        first = req.first_token_time is None
        if first:
            req.first_token_time = time.perf_counter()
            self.metrics.record_ttft(req.ttft)
        # prefill phase -> decode phase on the first token of THIS
        # admission (cheap no-op when already decoding)
        self.sched.note_decoding(req)
        if first and self.tracer.enabled:
            self.tracer.instant("first_token", cat="request",
                                args={"rid": req.rid,
                                      "ttft_s": round(req.ttft, 6)})
        if req.eos_token_id is not None and token == req.eos_token_id:
            self._finish(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length")

    def _finish(self, req: Request, reason: str) -> None:
        slot = req.slot
        self.sched.finish(req, reason)
        self._clear_slot_arrays(slot)
        self.metrics.requests_completed += 1

    def _preempt(self, req: Request) -> None:
        slot = req.slot
        self.sched.preempt(req)
        self._clear_slot_arrays(slot)
        self.metrics.preemptions += 1

    # -- compiled programs ---------------------------------------------

    def _dequant(self, qparams):
        if self.engine._dequant_meta is None:
            return qparams
        from ...compression.quantization import dequantize_params

        return dequantize_params(qparams, self.engine._dequant_meta,
                                 self.engine.compute_dtype)

    def _build_mixed_step(self, t_tokens: Optional[int] = None):
        """The ONE resident serving program (one per packed width with
        ``mixed_step_buckets``). Shapes are fixed — a packed
        ``[1, t_tokens]`` ragged token batch against the full pool —
        and EVERYTHING ragged rides as data: per-token table rows and
        absolute positions, per-slot segment offsets/lengths, chunk
        starts, context lengths, block tables. Decode rows, speculative
        verify rows and prefill chunks share the unified ragged attention
        grid (``ops/pallas/ragged_attention.py`` on TPU, the packed XLA
        reference elsewhere). EVERY packed position is sampled (the
        multi-position harvest): the host gathers a decode row's one
        prediction, a verify row's ``k + 1`` predictions (the greedy
        accept-prefix input) or a final chunk's token one from the same
        ``[T]`` output — so any traffic mix, draft schedule, chunk
        schedule or cache-hit pattern reuses ONE executable."""
        module, scfg = self.engine.module, self.config
        if t_tokens is None:
            t_tokens = self._mixed_tokens
        R = scfg.max_batch_size
        name = self._mixed_name(t_tokens)

        # ds_mixed_step: the XLA module takes the function's name, which
        # (unlike the scopes inside, which are metadata) is in the compile
        # cache's key — a cached executable under other names is not reused
        @versioned
        def ds_mixed_step(params, pool, tables, ids, token_rows, append_pos,
                          row_start, row_len, chunk_start, context_len,
                          corrupt, rng):
            # trace-time side effect: runs once per XLA compile
            self.compile_counts["mixed_step"] += 1  # dslint: ignore[trace-closure-state] intentional trace-time compile counter (fires once per XLA compile)
            self.perf.note_compile(name)
            self.tracer.instant("xla_compile", cat="engine",
                                args={"kind": name})
            # stable trace names: every device operation of the resident
            # program sits under ds.mixed_step, the model's own ds.* scopes
            # inside it (docs/observability.md)
            with jax.named_scope("ds.mixed_step"):
                params = self._dequant(params)
                idx = paged_cache_index(tables, append_pos, context_len,
                                        chunk_start=chunk_start,
                                        token_rows=token_rows,
                                        query_start=row_start,
                                        query_len=row_len)
                logits, pool = module.apply({"params": params}, ids,
                                            cache=pool, cache_index=idx)
                # multi-position harvest: per-position logits (chaos NaN
                # applied per flagged row, as DATA) + per-row NaN/Inf flag
                # OR-reduced over each row's valid tokens — one poisoned
                # draft position quarantines its request, never the batch
                with jax.named_scope("ds.sample"):
                    lg, bad = harvest_packed_logits(logits, token_rows, R,
                                                    corrupt=corrupt)
                    tok = _sample_logits(lg, rng, scfg.do_sample,
                                         scfg.temperature, scfg.top_k,
                                         scfg.top_p)
                return tok.astype(jnp.int32), bad, pool

        # explicit shardings, exactly like the dense engine's generate: TP
        # params keep their NamedShardings, everything else replicates
        r = self.engine._replicated
        return jax.jit(ds_mixed_step, donate_argnums=self._donate,
                       in_shardings=(self.engine.param_shardings,)
                       + (r,) * 11,
                       out_shardings=(r, r, r))

    def _build_decode(self):
        module, scfg = self.engine.module, self.config

        def decode(params, pool, tables, seq_lens, last_tok, corrupt, rng):
            # trace-time side effect: runs once per XLA compile
            self.compile_counts["decode"] += 1  # dslint: ignore[trace-closure-state] intentional trace-time compile counter (fires once per XLA compile)
            self.perf.note_compile("decode")
            self.tracer.instant("xla_compile", cat="engine",
                                args={"kind": "decode"})
            params = self._dequant(params)
            idx = paged_cache_index(tables, seq_lens[:, None], seq_lens + 1)
            logits, pool = module.apply({"params": params},
                                        last_tok[:, None], cache=pool,
                                        cache_index=idx)
            last = logits[:, 0]
            # corrupt_logits chaos: NaN the flagged slots' logits as DATA
            # (the mask is an input, so the drill never recompiles)
            last = jnp.where(corrupt[:, None],
                             jnp.asarray(jnp.nan, last.dtype), last)
            # output guard: per-slot NaN/Inf flag, computed on-device
            bad = ~jnp.isfinite(last).all(axis=-1)
            nxt = _sample_logits(last, rng, scfg.do_sample,
                                 scfg.temperature, scfg.top_k, scfg.top_p)
            return nxt.astype(jnp.int32), bad, pool

        # explicit shardings, exactly like the dense engine's generate: TP
        # params keep their NamedShardings (the partitioner inserts the
        # psums), everything else — pool, tables, lens, tokens — replicates
        r = self.engine._replicated
        return jax.jit(decode, donate_argnums=self._donate,
                       in_shardings=(self.engine.param_shardings,
                                     r, r, r, r, r, r),
                       out_shardings=(r, r, r))

    def _build_prefill(self, t_bucket: int):
        module, scfg = self.engine.module, self.config

        def prefill(params, pool, table_row, ids, length, rng):
            self.compile_counts["prefill"] += 1  # dslint: ignore[trace-closure-state] intentional trace-time compile counter (fires once per XLA compile)
            self.perf.note_compile(f"prefill[{t_bucket}]")
            self.tracer.instant("xla_compile", cat="engine",
                                args={"kind": "prefill", "bucket": t_bucket})
            params = self._dequant(params)
            ar = jnp.arange(t_bucket)[None, :]
            append_pos = jnp.where(ar < length[:, None], ar, -1)
            idx = paged_cache_index(table_row, append_pos, length)
            logits, pool = module.apply({"params": params}, ids, cache=pool,
                                        cache_index=idx)
            last = jnp.take_along_axis(
                logits, (length - 1)[:, None, None], axis=1)[:, 0]
            bad = ~jnp.isfinite(last).all(axis=-1)
            tok = _sample_logits(last, rng, scfg.do_sample, scfg.temperature,
                                 scfg.top_k, scfg.top_p)
            return tok.astype(jnp.int32), bad, pool

        r = self.engine._replicated
        return jax.jit(prefill, donate_argnums=self._donate,
                       in_shardings=(self.engine.param_shardings,
                                     r, r, r, r, r),
                       out_shardings=(r, r, r))

    def _build_chunked_prefill(self):
        """The ONE resident chunked-prefill program. Shapes are fixed —
        ``[1, prefill_chunk_tokens]`` ids against the full pool — and the
        chunk's absolute offset, valid length, block table and (implicitly,
        through the table) cached-prefix length all ride as data, so chunk
        position 0 of a cold prompt and chunk 7 behind a long prefix hit
        run the SAME executable. ``chunk_start`` in the cache-index bundle
        switches the model's paged branch to pool attention (cached prefix
        + chunk), replacing the from-empty fresh-KV contract the bucketed
        prefill relies on."""
        module, scfg = self.engine.module, self.config
        t_chunk = self._chunk

        def chunked_prefill(params, pool, table_row, ids, start, length,
                            corrupt, rng):
            self.compile_counts["chunked_prefill"] += 1  # dslint: ignore[trace-closure-state] intentional trace-time compile counter (fires once per XLA compile)
            self.perf.note_compile("chunked_prefill")
            self.tracer.instant("xla_compile", cat="engine",
                                args={"kind": "chunked_prefill"})
            params = self._dequant(params)
            ar = jnp.arange(t_chunk)[None, :]
            append_pos = jnp.where(ar < length[:, None],
                                   start[:, None] + ar, -1)
            idx = paged_cache_index(table_row, append_pos, start + length,
                                    chunk_start=start)
            logits, pool = module.apply({"params": params}, ids, cache=pool,
                                        cache_index=idx)
            last = jnp.take_along_axis(
                logits, (length - 1)[:, None, None], axis=1)[:, 0]
            # corrupt_logits chaos (tag=serving_prefill): the flag is an
            # INPUT, so the drill never recompiles
            last = jnp.where(corrupt[:, None],
                             jnp.asarray(jnp.nan, last.dtype), last)
            bad = ~jnp.isfinite(last).all(axis=-1)
            tok = _sample_logits(last, rng, scfg.do_sample, scfg.temperature,
                                 scfg.top_k, scfg.top_p)
            return tok.astype(jnp.int32), bad, pool

        r = self.engine._replicated
        return jax.jit(chunked_prefill, donate_argnums=self._donate,
                       in_shardings=(self.engine.param_shardings,
                                     r, r, r, r, r, r, r),
                       out_shardings=(r, r, r))


def init_serving(model=None, config=None, serving_config=None, monitor=None,
                 **kwargs) -> ServingEngine:
    """Build an :class:`InferenceEngine` (same surface as
    ``deepspeed_tpu.init_inference``) and wrap it for serving."""
    from ..engine import init_inference

    engine = init_inference(model, config=config, **kwargs)
    return ServingEngine(engine, config=serving_config, monitor=monitor)
