"""Serving fleet router: N ServingEngine replicas behind one front door.

Everything below this layer is ONE engine on one mesh; this is the
scale-out story (DeepSpeed-MII's elastic multi-worker serving, reframed
for the paged jax engine): the router owns a FLEET-level admission queue
and dispatches each request onto one of N replicas — in-process replicas
for tests and benches, each with its own BlockPool, scheduler and admin
surface; the probe interface (``replica.Replica``) is exactly the bits
``monitor/export.py`` already serves over HTTP, so a cross-process fleet
scrapes instead of calling.

Routing is TWO-signal, never plain round-robin:

1. **prefix-cache affinity** — the router probes every candidate
   replica's content index for the longest :class:`~.block_pool.ChainKey`
   chain match on the incoming prompt (one hash pass serves every probe:
   chain keys compare by value across pools) and prefers the replica
   holding the most cached prefix — the request's prefill is mostly free
   there, and the fleet's aggregate hit rate compounds because each
   tenant's traffic keeps landing on the replica that already knows it;
2. **goodput weighting** — ties break (and affinity is CAPPED) by a load
   score built from the PR 8 control-plane signals: live queue depth +
   residents plus the rolling ``slo_burn_rate`` scaled into request
   units. A replica more than ``load_spill`` requests past the
   least-loaded one loses its affinity claim — a hot cache must not
   become a hot spot — and ``/readyz`` reasons (``draining`` /
   ``brownout`` / ``cold``) exclude or deprioritize candidates before
   any scoring happens.

Resilience (the fleet half of the overload/chaos ladder):

- a request REJECTED by every replica's admission control stays at the
  head of the router queue (fleet-level backpressure, FIFO preserved);
- a request stranded on a dying replica — watchdog-failed, shed by a
  replica-local drain, displaced, killed — re-enters the router queue
  and is re-dispatched carrying ``prompt + delivered tokens`` (the
  recompute-preemption resume semantics, one level up), bounded by
  ``max_redispatches``;
- replicas that go unhealthy (``/healthz`` wedge, stale heartbeat) are
  EJECTED from routing and re-admitted when the probe recovers; their
  replica-queued requests are cancelled back into the fleet queue while
  running residents are left to finish or fail on their own;
- ``kill_replica`` / ``revive_replica`` model process death + supervisor
  restart (the ``DS_FAULT=replica_kill`` chaos point drives them
  mid-traffic); a kill returns every page through the scheduler and
  drops the replica's prefix index, so ``check_consistent`` holds
  fleet-wide after any storm;
- ``drain_replica`` generalizes drain to fleet level: one replica stops
  admitting and runs dry while the rest absorb its shed queue.

Disaggregated prefill (``RouterConfig.prefill_replicas``, off by
default): dedicated prefill replicas run each prompt's chunked prefill
(+ first token), then the committed KV pages are handed to a decode
replica through the content index (``fleet.transfer_prefix_kv`` —
host-side page copy on CPU; the interface names (src pages, dst pages),
so a TPU transfer collective in the Big Send-off shape slots in without
touching the router). The decode replica's admission then MATCHES the
transferred prefix and computes only the tail.
"""

import dataclasses
import itertools
import os
import re
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ...monitor.registry import snapshot_items
from ...utils import fault_injection
from ...utils.logging import log_dist
from .block_pool import ChainKey
from .engine import ServingEngine
from .journal import RequestJournal
from .replica import Replica
from .scheduler import RejectedError, RequestState, TERMINAL_STATES

#: live routers in this process (weak — a dropped router vanishes);
#: ``ds_report``'s fleet section reads from here, like the engine and
#: admin-server registries. Same lock law: WeakSet iteration is
#: Python-level bytecode, so an unlocked list() races construction.
_live_routers_lock = threading.Lock()
_LIVE_ROUTERS: "weakref.WeakSet" = weakref.WeakSet()  # dslint: guarded-by=_live_routers_lock


def live_serving_routers() -> List["ServingRouter"]:
    """Strong refs to every live ServingRouter in this process."""
    with _live_routers_lock:
        return list(_LIVE_ROUTERS)


#: replica-terminal reasons the router treats as ITS OWN doing (the fleet
#: request continues elsewhere, subject to the redispatch budget) rather
#: than as the request's outcome
_REQUEUE_CANCEL_REASONS = ("replica_kill", "drained", "router_eject",
                           "shed_overload")


@dataclasses.dataclass
class RouterConfig:
    """Knobs of the fleet router (each replica keeps its own
    :class:`~.engine.ServingConfig`)."""

    #: "affinity" = prefix-cache-aware + goodput-weighted (the default);
    #: "load" = goodput/load only (no content-index probe);
    #: "round_robin" exists ONLY as the A/B control for benches — it is
    #: deliberately the policy this router was built to beat
    routing: str = "affinity"
    #: fleet-level admission bound: queued fleet requests beyond this are
    #: rejected at the router door (0 = unbounded)
    max_queue_depth: int = 0
    #: deadline applied to submits that do not pass their own (seconds)
    default_deadline_s: Optional[float] = None
    #: times a request may re-enter the fleet queue after being stranded
    #: (kill / watchdog / shed) before the router gives up on it
    max_redispatches: int = 3
    #: affinity cap: a replica more than this many requests (queue +
    #: residents + burn-scaled) past the least-loaded candidate loses its
    #: prefix-affinity claim — the goodput signal overrides the cache one
    load_spill: float = 4.0
    #: request-units one unit of ``slo_burn_rate`` adds to the load score
    #: (a replica burning its SLO budget reads as loaded even when its
    #: queue happens to be short)
    burn_weight: float = 8.0
    #: eject a replica whose engine HAS work but whose step counter has
    #: not advanced for this long (0 = heartbeat staleness off; the
    #: wedged-backend /healthz probe is always on)
    heartbeat_stale_s: float = 0.0
    #: replica indices dedicated to PREFILL (non-empty = disaggregated
    #: mode): new requests prefill there (+ first token), then their
    #: committed KV pages transfer to a decode replica (everyone else)
    prefill_replicas: Tuple[int, ...] = ()
    #: auto-revive a killed replica after this many router steps (models
    #: the supervisor restart a chaos storm relies on; None = manual
    #: ``revive_replica`` only)
    revive_after_steps: Optional[int] = None
    #: TOTAL-outage bound: after this many consecutive ticks with work
    #: queued, nothing in flight, and ZERO live replicas (and no
    #: auto-revive configured), queued requests fail terminal
    #: ``no_replicas`` — without it ``run()``/``drain()`` would spin
    #: forever when a storm kills the whole fleet. A step-driven server
    #: whose operator revives inside the bound is unaffected. None
    #: disables the bound (requests wait indefinitely).
    outage_fail_steps: Optional[int] = 50
    #: crash-safe request journal (``serving/journal.py``): with a
    #: directory set, every admission is fsync'd BEFORE the fleet door
    #: accepts, delivery watermarks and terminal verdicts append as the
    #: request progresses, and :meth:`ServingRouter.recover` replays the
    #: directory after process death — re-admitting every non-terminal
    #: request at its delivered-token watermark. None = no journal (the
    #: pre-PR-15 volatile router).
    journal_dir: Optional[str] = None
    #: journal segment rotation size (bytes)
    journal_segment_bytes: int = 1 << 20
    #: compact the journal every N router steps (sealed segments drop
    #: terminal-request records; empty ones are deleted). 0 = manual
    #: ``journal.compact()`` only
    journal_compact_every: int = 256


@dataclasses.dataclass
class FleetRequest:
    """One request's fleet-level record: the router's durable state, from
    which any replica serve can be (re)constructed — ``prompt + tokens``
    is the resume stream, exactly like scheduler preemption."""

    prompt: List[int]
    max_new_tokens: int
    #: REQUIRED — always minted by :meth:`ServingRouter._fresh_fid` (or
    #: a door-validated client rid). A default factory here would draw
    #: bare ``fleet-<n>`` ids that bypass the journal-collision skip a
    #: restarted process needs (its counter restarts at 0 while the
    #: journal still holds the previous incarnation's fleet-N ids).
    fid: str
    eos_token_id: Optional[int] = None
    priority: int = 0
    #: absolute ``time.perf_counter()`` stamp; None = no deadline
    deadline: Optional[float] = None
    state: RequestState = RequestState.QUEUED
    #: tokens DELIVERED to the router so far (a killed replica's
    #: undelivered tokens die with it and are re-generated; a
    #: watchdog-failed request's already-delivered tokens survive)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    #: current placement (None while in the fleet queue)
    replica: Optional[int] = None
    rid: Optional[str] = None
    #: every replica index this request was served on, in order
    served_on: List[int] = dataclasses.field(default_factory=list)
    redispatches: int = 0
    #: disaggregation phase: None (normal) | "prefill" | "decode"
    phase: Optional[str] = None
    #: True when this request was re-admitted by :meth:`recover` after a
    #: router-process death: its ``submit_time`` is the RECOVERY time
    #: (the original submit's perf_counter stamp died with the process),
    #: so TTFT accounting stays honest by carrying the flag instead of a
    #: fabricated latency — the terminal span and FleetOutput both show
    #: ``recovered=true``
    recovered: bool = False
    #: replica whose pool holds this request's committed prefill KV (the
    #: transfer source for the decode-phase dispatch)
    kv_source: Optional[int] = None
    submit_time: float = dataclasses.field(
        default_factory=time.perf_counter)
    dispatch_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    #: memoized ChainKey chain of ``resume_tokens`` for the affinity
    #: probe (content-derived, so valid until the resume stream GROWS —
    #: a blocked fleet-queue head must not re-hash its prompt every
    #: router tick; the engines still intern their own keys at submit)
    route_hashes: List[ChainKey] = dataclasses.field(
        default_factory=list, repr=False)
    route_hash_len: int = -1

    @property
    def done(self) -> bool:
        return self.state in TERMINAL_STATES

    @property
    def resume_tokens(self) -> List[int]:
        return self.prompt + self.tokens

    @property
    def remaining_new(self) -> int:
        return self.max_new_tokens - len(self.tokens)

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time


_fid_counter = itertools.count()

#: the auto-generated fid shape — client-supplied rids may not use it
#: (a collision would make one caller's "duplicate" another's request)
_RESERVED_FID_RE = re.compile(r"^fleet-\d+$")


@dataclasses.dataclass
class FleetOutput:
    fid: str
    state: str
    prompt: List[int]
    tokens: List[int]
    finish_reason: Optional[str]
    ttft_s: Optional[float]
    redispatches: int
    served_on: List[int]
    recovered: bool = False


@dataclasses.dataclass
class FleetMetrics:
    """Fleet-level counters (per-replica serving metrics stay on each
    engine; the Prometheus export labels those with ``replica=``)."""

    requests_submitted: int = 0
    requests_finished: int = 0
    requests_failed: int = 0
    requests_timeout: int = 0
    requests_cancelled: int = 0
    requests_rejected: int = 0
    #: stranded requests that re-entered the fleet queue (kill / watchdog
    #: / replica drain / displacement) — each is one survived incident
    requests_requeued: int = 0
    #: non-terminal requests re-admitted from the journal after a router
    #: process death — each is one request a crash did NOT lose
    requests_recovered: int = 0
    #: duplicate submits suppressed at the door (same rid already known
    #: to the router or its journal — client retries after a restart)
    duplicates_suppressed: int = 0
    #: completed rolling-restart cycles (every replica restarted once)
    rolling_restarts: int = 0
    #: dispatches routed because of a prefix-affinity match vs. pure
    #: load order (the policy's own effectiveness counters)
    routed_affinity: int = 0
    routed_load: int = 0
    replica_kills: int = 0
    replica_revives: int = 0
    ejections: int = 0
    readmissions: int = 0
    #: disaggregated mode: prefill->decode hops and KV pages handed over
    disagg_hops: int = 0
    kv_pages_transferred: int = 0
    #: elastic membership: completed scale transitions and the pages
    #: the scale-out warmup moved (device-sourced vs host-tier-sourced)
    scale_outs: int = 0
    scale_ins: int = 0
    scale_aborts: int = 0
    scale_warm_pages: int = 0
    scale_warm_pages_host: int = 0
    steps: int = 0
    # gauges
    queue_depth: int = 0
    in_flight: int = 0
    replicas_total: int = 0
    replicas_active: int = 0

    def snapshot(self) -> Dict[str, float]:
        return {f.name: float(getattr(self, f.name))
                for f in dataclasses.fields(self)}


class ServingRouter:
    """Fleet front door over N in-process :class:`ServingEngine` replicas.

    Drive with :meth:`submit` / :meth:`step` / :meth:`run` / :meth:`poll`
    — the same surface as one engine, one level up. Replicas may share
    one underlying :class:`InferenceEngine` (same params, per-replica
    KV pools) or bring their own.
    """

    def __init__(self, engines: List[ServingEngine],
                 config: Optional[RouterConfig] = None):
        if not engines:
            raise ValueError("ServingRouter needs at least one replica")
        self.cfg = config or RouterConfig()
        if self.cfg.routing not in ("affinity", "load", "round_robin"):
            raise ValueError(f"unknown routing policy {self.cfg.routing!r} "
                             f"(want affinity | load | round_robin)")
        block_sizes = {e.config.block_size for e in engines}
        if len(block_sizes) > 1:
            # one hash pass serves every replica's affinity probe (and
            # the disaggregated KV handoff) only when pages line up
            raise ValueError(f"replicas must share block_size for "
                             f"prefix-affinity routing (got {block_sizes})")
        self.replicas = [Replica(i, e) for i, e in enumerate(engines)]
        for i in self.cfg.prefill_replicas:
            if not 0 <= i < len(self.replicas):
                raise ValueError(f"prefill_replicas names replica {i}; "
                                 f"fleet has {len(self.replicas)}")
        if self.cfg.prefill_replicas and \
                len(set(self.cfg.prefill_replicas)) >= len(self.replicas):
            raise ValueError("disaggregation needs at least one replica "
                             "left for decode")
        self.metrics = FleetMetrics()
        #: dispatches per replica index — the routing table's history and
        #: the balanced-placement routing tiebreak. The admin scrape
        #: thread renders it, so readers off the router thread take a
        #: point-in-time copy (new keys appear as replicas first serve)
        self.routed_by_replica: Dict[int, int] = {}  # dslint: guarded-by=snapshot
        self.queue: "list[FleetRequest]" = []
        self._requests: Dict[str, FleetRequest] = {}
        #: fid -> (replica idx, replica rid) for every dispatched request.
        #: The admin scrape thread reads it for gauges, so readers outside
        #: the router thread must materialize a point-in-time copy
        self._placements: Dict[str, Tuple[int, str]] = {}  # dslint: guarded-by=snapshot
        self._step_no = 0
        self._draining = False
        self._rr = 0
        #: spawns ONE fresh ServingEngine for elastic scale-out beyond
        #: the constructed fleet (set by :func:`init_fleet`; None =
        #: scale-out can only reactivate retired slots)
        self.replica_factory: Optional[Callable[[], ServingEngine]] = None
        #: fleet-hottest prefix chains: deepest route-hash key of each
        #: affinity dispatch, LRU-bounded — the scale-out warmup's
        #: shopping list (which prefixes are worth pre-transferring onto
        #: a replica that has served nothing yet)
        self._chain_heat: "OrderedDict[ChainKey, int]" = OrderedDict()
        self._chain_heat_cap = 64
        #: replica idx -> reason for every scale-in whose drain is still
        #: running dry; :meth:`step` completes (retire + journal done)
        #: or aborts (killed mid-drain) each one
        self._pending_scale_in: Dict[int, str] = {}
        #: consecutive ticks of total outage (queue blocked, no live
        #: replica) — drives the outage_fail_steps terminal bound
        self._outage_steps = 0
        #: crash-safe request journal (None = volatile). Opening it
        #: replays any existing segments (truncating a torn tail), so a
        #: restarted router can immediately :meth:`recover`
        self.journal: Optional[RequestJournal] = None
        if self.cfg.journal_dir:
            self.journal = RequestJournal(
                self.cfg.journal_dir,
                segment_bytes=self.cfg.journal_segment_bytes)
        with _live_routers_lock:
            _LIVE_ROUTERS.add(self)
        log_dist(f"ServingRouter: {len(self.replicas)} replicas, "
                 f"routing={self.cfg.routing}"
                 + (f", prefill_replicas={list(self.cfg.prefill_replicas)}"
                    if self.cfg.prefill_replicas else ""), ranks=[0])

    # ------------------------------------------------------------------
    # public API (one engine's surface, one level up)
    # ------------------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: int = 16,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0, rid: Optional[str] = None) -> str:
        """Enqueue on the FLEET queue; returns the fleet request id.
        Raises :class:`RejectedError` when the router door refuses
        (fleet queue full / fleet draining). ``rid`` lets a caller name
        the request (client-supplied idempotency key): a rid the router
        already knows — live, terminal, or recovered from the journal —
        is suppressed at the door and its EXISTING id returned, so a
        client retrying its submit after a router restart can never
        double-admit (and never receives the same tokens twice)."""
        if rid is not None:
            # ORDER MATTERS: known-rid suppression first — retrying a
            # router-ISSUED fleet-N fid is the legitimate idempotent
            # retry (the client got that id from us) and must return
            # the existing request. Only an UNKNOWN fleet-N rid is a
            # squat on the auto-fid namespace and is rejected. (Like
            # poll(), retry-by-rid has no caller authentication — a
            # caller presenting another's id gets that request; keys
            # are capability tokens here.)
            if self._known_rid(rid):
                self.metrics.duplicates_suppressed += 1
                if rid not in self._requests:
                    # journal-known only (retry after a restart before
                    # recover(), or after forget() released the record):
                    # materialize it so poll()/forget() can answer for
                    # the id we are about to hand back — a terminal
                    # entry becomes a terminal record, a non-terminal
                    # one re-enters the queue at its watermark
                    self._materialize_entry(
                        self.journal.state[rid],
                        time.time())  # dslint: ignore[determinism] wall clock of record: journaled deadlines are wall-clock so they survive the process
                return rid
            if _RESERVED_FID_RE.match(rid):
                raise ValueError(
                    f"rid {rid!r} uses the reserved fleet-<n> namespace; "
                    f"pick a client-side key shape")
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # fleet-door capacity validation (mirrors ServingEngine.submit):
        # a request NO replica could ever hold must raise HERE, at the
        # caller — reaching dispatch it would raise out of step() and
        # strand everything else in flight. A request only SOME replicas
        # can hold is admitted; dispatch skips the too-small ones.
        err = self._capacity_error(len(prompt), max_new_tokens)
        if err is not None:
            raise ValueError(err)
        if self._draining:
            self.metrics.requests_rejected += 1
            raise RejectedError("draining", "fleet is draining; "
                                "no new admissions")
        if self.cfg.max_queue_depth and \
                len(self.queue) >= self.cfg.max_queue_depth:
            self.metrics.requests_rejected += 1
            raise RejectedError(
                "queue_full", f"fleet queue depth {len(self.queue)} at "
                f"cap {self.cfg.max_queue_depth}")
        if deadline_s is None:
            deadline_s = self.cfg.default_deadline_s
        deadline = None if deadline_s is None \
            else time.perf_counter() + float(deadline_s)
        freq = FleetRequest(prompt=prompt, max_new_tokens=max_new_tokens,
                            eos_token_id=eos_token_id, priority=int(priority),
                            deadline=deadline,
                            fid=rid if rid is not None else self._fresh_fid(),
                            phase="prefill" if self.cfg.prefill_replicas
                            else None)
        if self.journal is not None:
            # write-ahead: the admission is DURABLE (fsync'd) before the
            # door accepts — a crash from here on recovers this request.
            # Deadlines are journaled in wall-clock (perf_counter stamps
            # die with the process)
            self.journal.append_admit(
                freq.fid, prompt, max_new_tokens,
                eos_token_id=eos_token_id, priority=int(priority),
                deadline_wall=None if deadline_s is None
                else time.time() + float(deadline_s))  # dslint: ignore[determinism] wall clock of record: the journal's deadline must survive the process
        self.queue.append(freq)
        self._requests[freq.fid] = freq
        self.metrics.requests_submitted += 1
        return freq.fid

    def _capacity_error(self, prompt_len: int,
                        max_new_tokens: int) -> Optional[str]:
        """Why NO replica could ever hold a request of this shape (None
        = at least one can). The fleet door raises on it; recovery fails
        the request terminal instead — a journaled request from a
        bigger-configured previous incarnation must not wedge the FIFO
        queue of a fleet that can never serve it."""
        total = prompt_len + max_new_tokens
        if total > max(r.engine.config.max_model_len
                       for r in self.replicas):
            return (f"prompt ({prompt_len}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds every replica's "
                    f"max_model_len (largest: "
                    f"{max(r.engine.config.max_model_len for r in self.replicas)})")
        if not any(r.engine.block_pool.blocks_for_tokens(total)
                   <= min(r.engine.nb_max, r.engine.block_pool.num_blocks)
                   for r in self.replicas):
            return (f"request needs "
                    f"{self.replicas[0].engine.block_pool.blocks_for_tokens(total)} "
                    f"KV blocks at its length cap; no replica's pool "
                    f"serves that many per sequence (raise "
                    f"num_blocks/max_model_len)")
        return None

    def _known_rid(self, rid: str) -> bool:
        """Duplicate suppression at the fleet door: the router retains
        it, or the journal still tracks it. The window is BOUNDED by the
        journal's terminal-state retention (the newest ~64k terminals;
        see ``RequestJournal.prune_terminal_state``) and HOLDS across
        restarts — compaction keeps each terminal's verdict on disk as
        a tombstone until its entry ages out of that window. A retry
        older than the window can re-admit."""
        return rid in self._requests or \
            (self.journal is not None and self.journal.knows(rid))

    def _fresh_fid(self) -> str:
        """An auto fid no live record, journal record, or client rid
        already uses. The counter is process-local, so after a restart
        it RESTARTS while the journal still holds the previous
        incarnation's fleet-N ids — without the skip, a new request
        would silently collide with a recovered one (never journaled,
        its delivers folding into the dead entry)."""
        fid = f"fleet-{next(_fid_counter)}"
        while self._known_rid(fid):
            fid = f"fleet-{next(_fid_counter)}"
        return fid

    def try_submit(self, prompt_ids, max_new_tokens: int = 16,
                   eos_token_id: Optional[int] = None,
                   deadline_s: Optional[float] = None,
                   priority: int = 0) -> Optional[str]:
        """None instead of RejectedError when the router door sheds."""
        try:
            return self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                               eos_token_id=eos_token_id,
                               deadline_s=deadline_s, priority=priority)
        except RejectedError:
            return None

    def poll(self, fid: str) -> FleetOutput:
        freq = self._requests[fid]
        return FleetOutput(fid=freq.fid, state=freq.state.value,
                           prompt=list(freq.prompt),
                           tokens=list(freq.tokens),
                           finish_reason=freq.finish_reason,
                           ttft_s=freq.ttft,
                           redispatches=freq.redispatches,
                           served_on=list(freq.served_on),
                           recovered=freq.recovered)

    def cancel(self, fid: str, reason: str = "cancelled") -> bool:
        """Cancel from any live state (False once terminal). A dispatched
        request is cancelled on its replica the same call."""
        # fold any already-terminal replica outcome in first: a request
        # that finished last step but was not yet collected must report
        # FINISHED, not be clobbered to CANCELLED
        self._collect()
        freq = self._requests[fid]
        if freq.done:
            return False
        if freq.fid in self._placements:
            idx, rid = self._placements.pop(freq.fid)
            rep = self.replicas[idx]
            rep.engine.cancel(rid, "fleet_cancel")
            # the cancelled segment's partial tokens were already
            # delivered to the caller's stream: keep them on the record
            self._deliver(freq, rep.engine.forget(rid))
        elif freq in self.queue:
            self.queue.remove(freq)
        self._fleet_release(freq, RequestState.CANCELLED, reason)
        return True

    def forget(self, fid: str) -> FleetOutput:
        """Release the router's retained state for a request (cancelling
        it first when still live); returns the final output."""
        freq = self._requests[fid]
        if not freq.done:
            self.cancel(fid, "forgotten")
        out = self.poll(fid)
        del self._requests[fid]
        return out

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self._placements)

    def run(self, max_steps: Optional[int] = None
            ) -> Dict[str, FleetOutput]:
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return {fid: self.poll(fid) for fid in self._requests}

    def drain(self, max_steps: Optional[int] = None
              ) -> Dict[str, FleetOutput]:
        """Fleet-level drain: stop fleet admission and run everything in
        flight (and queued) to a terminal state. ``resume_admission()``
        reopens the door."""
        self._draining = True
        return self.run(max_steps=max_steps)

    def resume_admission(self) -> None:
        self._draining = False

    # -- crash recovery (the journal's read side) ----------------------

    def recover(self, journal_dir: Optional[str] = None) -> List[str]:
        """Replay the request journal after router-process death and
        re-admit every non-terminal request at its delivered-token
        watermark (``prompt + delivered`` is the resume stream — the
        recompute-resume semantics replica kills already proved, lifted
        to process death; greedy traffic is token-identical to an
        undisturbed run). Terminal journal entries are materialized as
        terminal fleet records so ``poll`` answers for them and a client
        retry of a finished rid is suppressed at the door instead of
        re-served. Returns the re-admitted fids, in admit order.

        Recovered requests carry ``recovered=True`` (FleetOutput, the
        replica-side terminal span) and their ``submit_time`` is the
        RECOVERY time — the honest TTFT stance: the original submit's
        monotonic stamp died with the old process, and a fabricated
        cross-process latency would poison the percentiles. Deadlines DO
        survive (journaled in wall-clock): a request whose budget
        expired during the outage times out here, it does not rise from
        the dead."""
        if journal_dir is not None:
            if self.journal is None:
                self.journal = RequestJournal(
                    journal_dir,
                    segment_bytes=self.cfg.journal_segment_bytes)
            elif os.path.abspath(self.journal.dir) != \
                    os.path.abspath(journal_dir):
                raise ValueError(
                    f"recover({journal_dir!r}): this router already "
                    f"journals to {self.journal.dir!r}")
        if self.journal is None:
            raise ValueError("recover() needs a journal: set "
                             "RouterConfig.journal_dir or pass "
                             "journal_dir")
        now_wall = time.time()  # dslint: ignore[determinism] wall clock of record: journaled deadlines are wall-clock so they survive the process
        self._reconcile_scale_state()
        recovered: List[str] = []
        for ent in list(self.journal.state.values()):
            if self._materialize_entry(ent, now_wall):
                recovered.append(ent.fid)
        self.journal.compact()
        if recovered:
            log_dist(f"fleet: recovered {len(recovered)} non-terminal "
                     f"request(s) from {self.journal.dir} "
                     f"(delivered-token watermarks carried)", ranks=[0])
        return recovered

    def _reconcile_scale_state(self) -> None:
        """Settle the journaled fleet membership after a crash so the
        recovered fleet is CONSISTENT: an unfinished scale-out leaves no
        ghost replica (aborted — the spawned engine died with the
        process anyway), an unfinished scale-in leaves the replica
        active (its drain died with the process; its requests recover
        independently through the request records), a journaled DONE
        governs — replicas scaled out beyond the constructed fleet are
        re-spawned, replicas scaled in are re-retired. Runs BEFORE
        request materialization so recovered requests dispatch onto the
        reconciled membership."""
        for idx, st in sorted(self.journal.scale_state.items()):
            pending = st.get("pending")
            if pending is not None:
                self.abort_scale(pending, idx, "crash_reconcile")
                self.metrics.scale_aborts += 1
                log_dist(f"fleet: recovery aborted unfinished "
                         f"scale-{pending} of replica {idx}", ranks=[0])
            active = st.get("active")
            if active is None:
                continue  # never completed a transition: base membership
            if active:
                while len(self.replicas) <= idx:
                    # journaled member beyond this fleet: re-spawn it
                    # (parked retired until ITS activation below — an
                    # intermediate index journaled inactive must come
                    # back retired, not alive)
                    self.replicas[self.add_replica()].retire()
                rep = self.replicas[idx]
                if rep.retired or not rep.alive:
                    rep.activate()
            elif idx < len(self.replicas):
                rep = self.replicas[idx]
                if not rep.retired:
                    if rep.engine.has_work():
                        # a fresh recovery fleet is dry; a LIVE router
                        # asked to re-reconcile mid-traffic must not
                        # cancel residents — leave it to scale_in
                        continue
                    rep.retire()

    def _materialize_entry(self, ent, now_wall: float) -> bool:
        """Materialize ONE journal entry into the router's request table
        (idempotent — an fid already held is left alone): terminal
        entries become terminal fleet records (``poll`` answers, retries
        suppress, nothing transitions), non-terminal ones re-enter the
        fleet queue at their delivered-token watermark — or go terminal
        right here when the journaled wall-clock deadline expired during
        the outage, every token was already delivered, or no replica of
        THIS fleet can hold them. Returns True only for a re-queued
        (live-recovered) entry. Shared by :meth:`recover` and the door's
        duplicate suppression (a journal-known rid must be answerable by
        ``poll`` the moment ``submit`` returns it)."""
        if ent.fid in self._requests:
            return False
        if ent.done:
            # materialized, not transitioned: the terminal happened
            # in the previous incarnation and is already journaled —
            # this just lets poll()/retries answer for it
            try:
                state = RequestState(ent.state)
                reason = ent.reason
            except ValueError:
                # a NEWER writer's terminal vocabulary (journal._fold
                # keeps unknown states verbatim for exactly this
                # rollback case) — degrade to FAILED with the foreign
                # verdict in the reason instead of aborting recovery
                # and losing every remaining non-terminal request
                state = RequestState.FAILED
                reason = f"journal-state:{ent.state}"
            self._requests[ent.fid] = FleetRequest(
                prompt=list(ent.prompt),
                max_new_tokens=ent.max_new_tokens,
                eos_token_id=ent.eos_token_id, priority=ent.priority,
                fid=ent.fid, state=state,
                tokens=list(ent.tokens),
                finish_reason=reason, recovered=True)
            return False
        remaining = None if ent.deadline_wall is None \
            else ent.deadline_wall - now_wall
        freq = FleetRequest(
            prompt=list(ent.prompt),
            max_new_tokens=ent.max_new_tokens,
            eos_token_id=ent.eos_token_id, priority=ent.priority,
            fid=ent.fid, tokens=list(ent.tokens),
            deadline=None if remaining is None
            else time.perf_counter() + remaining,
            phase="prefill" if self.cfg.prefill_replicas else None,
            recovered=True)
        self._requests[ent.fid] = freq
        if remaining is not None and remaining <= 0:
            # the deadline expired during the outage
            self._fleet_release(freq, RequestState.TIMEOUT, "deadline")
            return False
        hit_eos = ent.eos_token_id is not None and ent.tokens and \
            ent.tokens[-1] == ent.eos_token_id
        if freq.remaining_new <= 0 or hit_eos:
            # every token was delivered; only the terminal record
            # was lost to the crash — finish, deliver nothing twice
            self._fleet_release(freq, RequestState.FINISHED,
                                "eos" if hit_eos else "length")
            return False
        if self._capacity_error(len(freq.prompt),
                                freq.max_new_tokens) is not None:
            # journaled by a bigger-configured incarnation: THIS
            # fleet can never hold it — fail terminal instead of
            # wedging the FIFO queue head forever (submit raises
            # the same condition back at the caller)
            self._fleet_release(freq, RequestState.FAILED,
                                "capacity")
            return False
        self.queue.append(freq)
        self.metrics.requests_recovered += 1
        return True

    # -- replica lifecycle ---------------------------------------------

    def kill_replica(self, idx: int, reason: str = "replica_kill") -> int:
        """Abrupt replica death (chaos drill / operator action): every
        in-flight request there re-enters the fleet queue (undelivered
        tokens die with the process and are re-generated elsewhere), its
        pages return, its prefix index drops. Returns the number of
        stranded requests requeued."""
        rep = self.replicas[idx]
        was_alive = rep.alive
        stranded = rep.kill(self._step_no, reason)
        if was_alive:
            self.metrics.replica_kills += 1
        log_dist(f"fleet: replica {rep.name} killed "
                 f"({len(stranded)} in-flight requeued)", ranks=[0])
        # the cancelled requests are collected (and requeued) on the spot
        # so a same-step revive cannot race their re-dispatch
        self._collect()
        return len(stranded)

    def revive_replica(self, idx: int) -> None:
        rep = self.replicas[idx]
        if rep.alive or rep.retired:
            # a retired slot is a JOURNALED membership decision — only a
            # journaled scale-out reopens it, never the supervisor path
            return
        rep.revive()
        self.metrics.replica_revives += 1
        log_dist(f"fleet: replica {rep.name} revived", ranks=[0])

    def drain_replica(self, idx: int) -> int:
        """Drain ONE replica while the rest absorb: it stops admitting,
        its replica-queued requests re-enter the fleet queue, and its
        residents run dry in the normal step loop. Returns the number of
        requests shed back to the fleet."""
        rep = self.replicas[idx]
        shed = rep.begin_drain()
        self._collect()
        return len(shed)

    def undrain_replica(self, idx: int) -> None:
        self.replicas[idx].end_drain()

    # -- elastic membership (the autoscaler's scale-out/in ladders) ----
    #
    # Every transition is WRITE-AHEAD journaled: intent before any state
    # changes, done after the transition completed, abort when it was
    # interrupted (kill mid-drain, crash mid-scale). begin/commit/
    # abort_scale are the ONLY callers of journal.append_scale — the
    # dslint seam rule enforces it, the same law as the terminal funnel.

    def begin_scale(self, op: str, idx: int, reason: str) -> None:
        if self.journal is not None:
            self.journal.append_scale(op, idx, "intent", reason=reason)

    def commit_scale(self, op: str, idx: int, reason: str = "") -> None:
        if self.journal is not None:
            self.journal.append_scale(op, idx, "done", reason=reason)

    def abort_scale(self, op: str, idx: int, reason: str = "") -> None:
        if self.journal is not None:
            self.journal.append_scale(op, idx, "abort", reason=reason)

    def add_replica(self) -> int:
        """Append ONE fresh replica slot via :attr:`replica_factory`
        (raises without one). The new replica starts ACTIVE — callers
        wanting a parked slot retire it. No journaling here: this is the
        mechanism; :meth:`scale_out` / recovery own the record."""
        if self.replica_factory is None:
            raise RuntimeError(
                "add_replica needs replica_factory (init_fleet sets it; "
                "a hand-built router must provide its own)")
        eng = self.replica_factory()
        if eng.config.block_size != \
                self.replicas[0].engine.config.block_size:
            raise ValueError("replica_factory produced a mismatched "
                             "block_size; the affinity probe and KV "
                             "transfer both require one page geometry")
        idx = len(self.replicas)
        self.replicas.append(Replica(idx, eng))
        return idx

    def scale_out(self, reason: str = "autoscale",
                  warm_chains: int = 8) -> int:
        """Grow the fleet by one replica — reusing the lowest retired
        slot when one exists (its resident compile survives in-process;
        reactivation is why no scale event ever pays a recompile),
        spawning through :attr:`replica_factory` otherwise — then
        pre-warm its prefix cache from the fleet's hottest chains
        (:meth:`warm_replica`). Journaled intent -> activate -> warm ->
        done; a crash anywhere inside recovers to NO ghost replica
        (recovery aborts the unfinished intent). Returns the replica
        index scaled out."""
        idx = next((r.idx for r in self.replicas if r.retired), None)
        fresh = idx is None
        if fresh:
            if self.replica_factory is None:
                raise RuntimeError(
                    "scale_out: no retired slot to reuse and no "
                    "replica_factory to spawn one")
            idx = len(self.replicas)
        self.begin_scale("out", idx, reason)
        try:
            if fresh:
                self.add_replica()
            rep = self.replicas[idx]
            rep.activate()
            self.warm_replica(idx, top_k=warm_chains)
        except BaseException:
            self.abort_scale("out", idx, "error")
            self.metrics.scale_aborts += 1
            raise
        self.commit_scale("out", idx, reason)
        self.metrics.scale_outs += 1
        log_dist(f"fleet: scaled out {rep.name} "
                 f"({'fresh' if fresh else 'reactivated'}, {reason})",
                 ranks=[0])
        return idx

    def scale_in(self, idx: int, reason: str = "autoscale") -> bool:
        """Begin removing one replica: journal the intent, then compose
        the existing drain ladder — its queued work re-enters the fleet
        (requeued, never dropped), its residents run dry in the normal
        step loop, and :meth:`step` retires the slot (pages returned,
        caches dropped, admission closed) once dry, journaling the done.
        A kill mid-drain aborts the transition instead (the kill/revive
        path owns the replica from there). Returns False without acting
        when the replica is not scalable-in (already retired/dead/
        pending, or it is the last active replica)."""
        rep = self.replicas[idx]
        active = [r for r in self.replicas
                  if r.alive and not r.retired]
        if (rep.retired or not rep.alive or idx in self._pending_scale_in
                or len(active) <= 1):
            return False
        self.begin_scale("in", idx, reason)
        self._pending_scale_in[idx] = reason
        shed = self.drain_replica(idx)
        log_dist(f"fleet: scale-in of {rep.name} begun "
                 f"({shed} shed, {reason}); draining dry", ranks=[0])
        return True

    def _complete_pending_scale_ins(self) -> None:
        """Advance every in-flight scale-in one tick: retire replicas
        whose drain ran dry (journal done), abort transitions a kill
        interrupted (the drain intent died with the process — auto-
        revive must bring the replica back ROUTABLE, not half-retired)."""
        for idx, reason in list(self._pending_scale_in.items()):
            rep = self.replicas[idx]
            if not rep.alive or not rep.draining:
                # killed (or externally undrained) mid-drain: the
                # ladder is off — journal the abort so recovery never
                # half-retires this slot
                del self._pending_scale_in[idx]
                self.abort_scale("in", idx, "interrupted")
                self.metrics.scale_aborts += 1
                log_dist(f"fleet: scale-in of {rep.name} aborted "
                         f"(interrupted mid-drain)", ranks=[0])
                continue
            if rep.engine.has_work():
                continue
            del self._pending_scale_in[idx]
            rep.retire()
            self.commit_scale("in", idx, reason)
            self.metrics.scale_ins += 1
            log_dist(f"fleet: {rep.name} retired (scale-in complete, "
                     f"{reason})", ranks=[0])

    def warm_replica(self, idx: int, top_k: int = 8) -> Tuple[int, int]:
        """Deliberate scale-out warmup: pre-transfer the fleet's ``top_k``
        hottest prefix chains (the affinity dispatch record) onto replica
        ``idx`` from whichever live peer holds each — device pages via
        ``transfer_prefix_kv``, host-tier pages via
        ``transfer_host_prefix_kv``. The router's fewest-ever-routed
        tiebreak then finishes the slow-start with real traffic. Returns
        (device_pages, host_pages) moved; (0, 0) when nothing is hot or
        no peer can source (the new replica simply computes — correct,
        just colder)."""
        from .fleet import chain_tokens, warm_prefix_kv

        rep = self.replicas[idx]
        hot = sorted(self._chain_heat.items(), key=lambda kv: -kv[1])
        dev_total = host_total = 0
        for key, _ in hot[:top_k]:
            tokens = chain_tokens(key)
            for donor in self.replicas:
                if donor is rep or not donor.alive or donor.retired:
                    continue
                dev, host = warm_prefix_kv(donor.engine, rep.engine,
                                           tokens)
                dev_total += dev
                host_total += host
                if dev or host:
                    break  # this chain is warmed; next chain
        self.metrics.scale_warm_pages += dev_total
        self.metrics.scale_warm_pages_host += host_total
        if dev_total or host_total:
            log_dist(f"fleet: warmed {rep.name} with {dev_total} device "
                     f"+ {host_total} host-tier page(s) of hot prefix",
                     ranks=[0])
        return dev_total, host_total

    def rolling_restart(self, capacity_floor: Optional[int] = None,
                        max_steps_per_replica: int = 2000
                        ) -> Dict[str, Any]:
        """Deploy-time drill: restart EVERY replica, one at a time —
        ``drain_replica`` (its queued work re-enters the fleet, its
        residents run dry while the rest absorb) → kill (cold restart:
        pages return, both cache tiers drop) → revive — so the fleet
        never serves below ``capacity_floor`` live replicas (default
        N-1: exactly one down at any moment). Requests never notice
        beyond latency: shed work re-serves elsewhere with delivered
        tokens carried, the recompute-resume invariant end to end.

        Raises RuntimeError when a replica cannot drain (or the floor
        cannot be met) within ``max_steps_per_replica`` fleet ticks —
        a stuck rolling restart must fail loudly, not spin."""
        # retired slots are OUT of the fleet by journaled decision: they
        # are neither restarted nor counted against the capacity floor
        members = [r for r in self.replicas if not r.retired]
        n = len(members)
        if n == 0:
            raise RuntimeError("rolling restart: every replica is "
                               "retired; scale out first")
        floor = n - 1 if capacity_floor is None else int(capacity_floor)
        if not 0 <= floor <= n - 1:
            raise ValueError(
                f"capacity_floor must be in [0, {n - 1}] (one replica "
                f"must be restartable), got {floor}")
        restarted: List[str] = []
        shed_total = 0
        for rep in members:
            steps = 0
            # the capacity floor gates the takedown, not the drain: wait
            # out delayed auto-revives before touching the next replica
            while sum(r.alive for r in self.replicas) \
                    - (1 if rep.alive else 0) < floor:
                self.step()
                steps += 1
                if steps > max_steps_per_replica:
                    raise RuntimeError(
                        f"rolling restart: capacity floor {floor} "
                        f"unreachable before restarting {rep.name}")
            if rep.alive:
                shed_total += self.drain_replica(rep.idx)
                steps = 0
                while rep.engine.has_work():
                    self.step()
                    steps += 1
                    if steps > max_steps_per_replica:
                        raise RuntimeError(
                            f"rolling restart: replica {rep.name} never "
                            f"ran dry ({max_steps_per_replica} ticks)")
                self.kill_replica(rep.idx, reason="rolling_restart")
            self.revive_replica(rep.idx)
            restarted.append(rep.name)
        self.metrics.rolling_restarts += 1
        log_dist(f"fleet: rolling restart complete "
                 f"({len(restarted)} replicas, {shed_total} shed, "
                 f"floor {floor})", ranks=[0])
        return {"restarted": restarted, "shed": shed_total,
                "capacity_floor": floor}

    # ------------------------------------------------------------------
    # one router tick
    # ------------------------------------------------------------------

    def step(self) -> None:
        """One fleet tick: chaos probes -> health sweep -> deadline sweep
        -> dispatch from the fleet queue -> step every live replica ->
        collect terminals (requeueing the stranded)."""
        self._chaos_probe()
        self._health_sweep()
        self._expire_queued()
        self._dispatch()
        for rep in self.replicas:
            if rep.alive and rep.engine.has_work():
                rep.engine.step()
            rep.note_progress()
        self._collect()
        self._complete_pending_scale_ins()
        self._check_total_outage()
        self._step_no += 1
        if self.journal is not None and self.cfg.journal_compact_every \
                and self._step_no % self.cfg.journal_compact_every == 0:
            # steady-state hygiene: sealed segments shed their terminal
            # records so the journal tracks the LIVE set, not traffic
            self.journal.compact()
            self.journal.prune_terminal_state()
        m = self.metrics
        m.steps += 1
        m.queue_depth = len(self.queue)
        m.in_flight = len(self._placements)
        m.replicas_total = len(self.replicas)
        m.replicas_active = sum(1 for r in self.replicas
                                if r.alive and not r.retired)

    def _check_total_outage(self) -> None:
        """Bound the whole-fleet-dead livelock: with work queued, nothing
        in flight, zero live replicas and no supervisor auto-revive,
        nothing can ever progress — past ``outage_fail_steps`` ticks the
        queued requests fail terminal ``no_replicas`` so drive loops
        terminate instead of spinning."""
        total_outage = bool(self.queue) and not self._placements and \
            not any(r.alive for r in self.replicas) and \
            self.cfg.revive_after_steps is None
        if not total_outage:
            self._outage_steps = 0
            return
        self._outage_steps += 1
        if self.cfg.outage_fail_steps is None or \
                self._outage_steps <= self.cfg.outage_fail_steps:
            return
        log_dist(f"fleet: total outage for {self._outage_steps} ticks "
                 f"with no auto-revive; failing {len(self.queue)} queued "
                 f"request(s)", ranks=[0])
        for freq in list(self.queue):
            self.queue.remove(freq)
            self._fleet_release(freq, RequestState.FAILED, "no_replicas")
        self._outage_steps = 0

    def _chaos_probe(self) -> None:
        """``DS_FAULT=replica_kill[:replica=N][:step=K]`` kills one
        replica mid-traffic (the storm drill). A malformed or dead pin
        falls back to the first live replica — an injection point must
        never crash the loop it is drilling.

        ``DS_FAULT=router_crash:tag=serving_fleet[:step=K]`` kills THE
        ROUTER PROCESS itself (``os._exit`` — models kill -9 / OOM, no
        flush beyond what the journal already fsync'd): the crash drill
        behind ``ServingRouter.recover`` — the bench and the chaos
        fuzzer arm it in a subprocess and recover in the parent."""
        fault_injection.maybe_crash("router_crash", tag="serving_fleet",
                                    step=self._step_no)
        spec = fault_injection.maybe_flag("replica_kill",
                                          tag="serving_fleet",
                                          step=self._step_no)
        if spec is None:
            return
        alive = [r.idx for r in self.replicas if r.alive]
        if not alive:
            return
        try:
            pin = int(spec.params["replica"])
        except (KeyError, ValueError):
            pin = alive[0]
        if pin not in alive:
            pin = alive[0]
        self.kill_replica(pin)

    def _health_sweep(self) -> None:
        """Eject unhealthy replicas (no NEW dispatches; their queued work
        returns to the fleet), re-admit recovered ones, auto-revive
        killed ones past the supervisor delay."""
        for rep in self.replicas:
            if not rep.alive:
                if self.cfg.revive_after_steps is not None and \
                        rep.killed_at_step is not None and \
                        self._step_no - rep.killed_at_step >= \
                        self.cfg.revive_after_steps:
                    self.revive_replica(rep.idx)
                continue
            healthy, reasons = rep.probe_health(self.cfg.heartbeat_stale_s)
            if not healthy and not rep.ejected:
                rep.ejected = True
                rep.ejections += 1
                self.metrics.ejections += 1
                log_dist(f"fleet: replica {rep.name} ejected "
                         f"({','.join(reasons)})", ranks=[0])
                # replica-queued work must not wait out the incident:
                # cancel it back into the fleet queue (running residents
                # are left to finish or fail on their own — the replica's
                # watchdog owns them)
                for fid, (idx, rid) in list(self._placements.items()):
                    if idx != rep.idx:
                        continue
                    if rep.engine.request(rid).state is RequestState.QUEUED:
                        rep.engine.cancel(rid, "router_eject")
            elif healthy and rep.ejected:
                rep.ejected = False
                rep.readmissions += 1
                self.metrics.readmissions += 1
                log_dist(f"fleet: replica {rep.name} re-admitted", ranks=[0])

    def _expire_queued(self) -> None:
        now = time.perf_counter()
        for freq in [f for f in self.queue
                     if f.deadline is not None and now > f.deadline]:
            self.queue.remove(freq)
            self._fleet_release(freq, RequestState.TIMEOUT, "deadline")

    # -- routing -------------------------------------------------------

    def _candidates(self, phase: Optional[str]) -> List[Replica]:
        """Dispatchable replicas for this phase. ``/readyz`` semantics at
        fleet level: ``draining`` excludes, ``brownout`` deprioritizes
        (used only when nothing else can take the request), and ``cold``
        deliberately does NOT — the balanced-placement tiebreak in
        :meth:`_route` warms spare replicas on idle ties, because a fleet
        whose spares never warm cannot absorb a kill storm (an EXTERNAL
        LB fronting latency-critical traffic is what the cold bit is
        for)."""
        reps = self.replicas
        if self.cfg.prefill_replicas:
            pset = set(self.cfg.prefill_replicas)
            want_prefill = phase == "prefill"
            reps = [r for r in reps if (r.idx in pset) == want_prefill]
        pairs = []
        for r in reps:
            if not r.routable:
                continue
            reasons = r.ready_reasons()
            if "draining" in reasons:
                continue
            pairs.append((r, "brownout" in reasons))
        full = [r for r, browned in pairs if not browned]
        return full or [r for r, _ in pairs]

    def _route(self, tokens: List[int], phase: Optional[str],
               hashes: Optional[List[ChainKey]] = None
               ) -> List[Tuple[int, Replica]]:
        """Ranked ``(prefix_match_tokens, replica)`` candidates, best
        first; dispatch walks the ranking until one replica's admission
        accepts. Ranking key: longest capped prefix match, then load
        score, then fewest-ever-routed (balanced placement — spreads
        idle ties and slow-starts cold replicas), then index. Pass the
        request's memoized ``hashes`` (``_prompt_hashes``) — dispatch
        retries the blocked head every tick and must not re-hash it."""
        pool = self._candidates(phase)
        if not pool:
            return []
        if self.cfg.routing == "round_robin":
            k = self._rr
            self._rr += 1
            return [(0, pool[(k + i) % len(pool)])
                    for i in range(len(pool))]
        loads = {r.idx: r.load_score(self.cfg.burn_weight) for r in pool}
        min_load = min(loads.values())
        if hashes is None and self.cfg.routing == "affinity":
            hashes = pool[0].engine.block_pool.prefix_block_hashes(tokens)
        hashes = hashes or []
        ranked = []
        for r in pool:
            pfx = r.prefix_match_tokens(tokens, hashes) if hashes else 0
            if loads[r.idx] > min_load + self.cfg.load_spill:
                # the affinity cap: past the spill threshold the cached
                # replica loses its claim and sorts purely by load —
                # a hot cache must not become a hot spot
                pfx = 0
            ranked.append((-pfx, loads[r.idx],
                           self.routed_by_replica.get(r.idx, 0),
                           r.idx, r))
        ranked.sort(key=lambda t: t[:4])
        return [(-t[0], t[4]) for t in ranked]

    def _dispatch(self) -> None:
        """Move fleet-queue heads onto replicas, FIFO: the head that no
        replica accepts stays put and blocks the queue (fleet-level
        backpressure — the same head-of-line law as engine admission)."""
        while self.queue:
            if not self._dispatch_one(self.queue[0]):
                return
            self.queue.pop(0)

    def _dispatch_one(self, freq: FleetRequest) -> bool:
        """Place one fleet request; True = the head was CONSUMED (placed,
        or released terminal) and may be popped, False = blocked (no
        replica accepts right now). Never touches the queue itself."""
        now = time.perf_counter()
        deadline_s = None
        if freq.deadline is not None:
            deadline_s = freq.deadline - now
            if deadline_s <= 0:
                self._fleet_release(freq, RequestState.TIMEOUT, "deadline")
                return True
        resume = freq.resume_tokens
        budget = 1 if freq.phase == "prefill" else freq.remaining_new
        for pfx, rep in self._route(resume, freq.phase,
                                    self._prompt_hashes(freq, resume)):
            try:
                rid = rep.engine.try_submit(resume, max_new_tokens=budget,
                                            eos_token_id=freq.eos_token_id,
                                            deadline_s=deadline_s,
                                            priority=freq.priority)
            except ValueError:
                # the fleet door validated that SOME replica can hold
                # this request; on a heterogeneous fleet this one is too
                # small for it — a capability mismatch, not a caller bug
                continue
            if rid is None:
                continue
            if freq.phase == "decode" and freq.kv_source is not None:
                # the handoff lands BETWEEN submit and the replica's next
                # step — admission matches the transferred prefix there
                self._handoff_kv(freq, rep)
            if freq.recovered:
                # the replica-side terminal span carries recovered=true,
                # so trace_view's TTFT/SLO breakdowns can separate
                # crash-replayed traffic from organic arrivals
                rep.engine.request(rid).recovered = True
            freq.replica, freq.rid = rep.idx, rid
            freq.served_on.append(rep.idx)
            freq.state = RequestState.RUNNING
            freq.dispatch_time = now
            self._placements[freq.fid] = (rep.idx, rid)
            routed = self.routed_by_replica  # one field read (RMW below)
            routed[rep.idx] = routed.get(rep.idx, 0) + 1
            if freq.route_hashes:
                # hot-chain record for the scale-out warmup: the DEEPEST
                # chain key names the whole prefix, so one entry per
                # dispatched prompt, LRU-bounded (heat decays by falling
                # off the cold end, not by clock — deterministic)
                heat = self._chain_heat
                key = freq.route_hashes[-1]
                heat[key] = heat.get(key, 0) + 1
                heat.move_to_end(key)
                while len(heat) > self._chain_heat_cap:
                    heat.popitem(last=False)
            if pfx > 0:
                self.metrics.routed_affinity += 1
            else:
                self.metrics.routed_load += 1
            return True
        return False

    def _prompt_hashes(self, freq: FleetRequest,
                       resume: List[int]) -> Optional[List[ChainKey]]:
        """The request's memoized affinity-probe chain, rebuilt only when
        the resume stream grew (requeue delivered tokens). None when the
        policy never probes the content index."""
        if self.cfg.routing != "affinity":
            return None
        if freq.route_hash_len != len(resume):
            freq.route_hashes = self.replicas[0].engine.block_pool \
                .prefix_block_hashes(resume)
            freq.route_hash_len = len(resume)
        return freq.route_hashes

    def _handoff_kv(self, freq: FleetRequest, rep: Replica) -> None:
        """Disaggregated prefill -> decode handoff: copy the committed
        prefix KV pages from the prefill replica's pool into the decode
        replica's, content-indexed so its admission matches them. A dead
        or missing source simply skips the transfer — the decode replica
        recomputes (correct, just slower), which is exactly the
        resilience story a storm needs."""
        from .fleet import transfer_prefix_kv

        src = self.replicas[freq.kv_source]
        freq.kv_source = None  # one handoff per hop, even on failure
        if not src.alive:
            return
        moved = transfer_prefix_kv(src.engine, rep.engine,
                                   freq.resume_tokens)
        self.metrics.kv_pages_transferred += moved

    # -- collection / requeue ------------------------------------------

    def _collect(self) -> None:
        """Fold replica-terminal requests back into fleet state: finishes
        deliver tokens (or hop prefill->decode), strandings requeue,
        deadline expiries time out."""
        for fid, (idx, rid) in list(self._placements.items()):
            rep = self.replicas[idx]
            req = rep.engine.request(rid)
            if not req.done:
                continue
            del self._placements[fid]
            freq = self._requests[fid]
            out = rep.engine.forget(rid)
            freq.replica, freq.rid = None, None
            if req.state is RequestState.FINISHED:
                self._on_finished(freq, out, rep)
            elif req.state is RequestState.TIMEOUT:
                # partial tokens were delivered before the deadline hit:
                # the fleet surface reports them like a bare engine does
                self._deliver(freq, out)
                self._fleet_release(freq, RequestState.TIMEOUT,
                                    out.finish_reason or "deadline")
            elif req.state is RequestState.CANCELLED and \
                    out.finish_reason not in _REQUEUE_CANCEL_REASONS:
                # caller-side cancel realized at the replica
                self._deliver(freq, out)
                self._fleet_release(freq, RequestState.CANCELLED,
                                    out.finish_reason or "cancelled")
            else:
                # stranded: killed / drained / ejected / displaced /
                # engine-side failure — the fleet serves it elsewhere.
                # A kill's undelivered tokens died with the process; any
                # other stranding happened in a live process whose tokens
                # were already delivered, so they carry over (resume)
                if out.finish_reason != "replica_kill":
                    self._deliver(freq, out)
                self._requeue(freq, out.finish_reason or req.state.value)
        if self.journal is not None:
            # land any batched watermark whose terminal has not followed
            # (requeued strandings) before the caller can observe tokens
            self.journal.flush()

    def _deliver(self, freq: FleetRequest, out) -> None:
        """Fold one replica segment's output into the fleet record. The
        fleet TTFT anchors on the REPLICA's measured first-token time
        (dispatch + its ttft), not on collection time — collection
        happens at segment end, which would inflate TTFT to total
        generation latency. With the journal armed the delivery
        watermark (token ids included) is made durable BEFORE the
        caller can observe the tokens: a recovery resumes at exactly
        this watermark, so no token is ever delivered twice."""
        if out.tokens and freq.first_token_time is None:
            if out.ttft_s is not None and freq.dispatch_time is not None:
                freq.first_token_time = freq.dispatch_time + out.ttft_s
            else:
                freq.first_token_time = time.perf_counter()
        if self.journal is not None and out.tokens:
            # batched fsync: most delivers are immediately followed by
            # the terminal append (one fsync covers both); stranded-
            # segment delivers are flushed at the end of _collect —
            # either way the record is on disk before step()/cancel()
            # returns control to a caller that could observe the tokens
            self.journal.append_deliver(freq.fid, list(out.tokens),
                                        sync=False)
        freq.tokens.extend(out.tokens)

    def _on_finished(self, freq: FleetRequest, out, rep: Replica) -> None:
        self._deliver(freq, out)
        hit_eos = freq.eos_token_id is not None and \
            bool(freq.tokens) and freq.tokens[-1] == freq.eos_token_id
        if freq.phase == "prefill" and not hit_eos \
                and freq.remaining_new > 0:
            # disaggregation hop: prefill (+ first token) done here; the
            # committed KV hands off to a decode replica at dispatch
            freq.phase = "decode"
            freq.kv_source = rep.idx
            freq.state = RequestState.QUEUED
            self.metrics.disagg_hops += 1
            self.queue.insert(0, freq)
            return
        reason = out.finish_reason or "length"
        if freq.remaining_new <= 0 and not hit_eos:
            reason = "length"
        self._fleet_release(freq, RequestState.FINISHED, reason)

    def _requeue(self, freq: FleetRequest, reason: str) -> None:
        if freq.remaining_new <= 0:
            self._fleet_release(freq, RequestState.FINISHED, "length")
            return
        if freq.deadline is not None and \
                time.perf_counter() > freq.deadline:
            self._fleet_release(freq, RequestState.TIMEOUT, "deadline")
            return
        freq.redispatches += 1
        if freq.redispatches > self.cfg.max_redispatches:
            self._fleet_release(freq, RequestState.FAILED,
                                f"redispatch_budget:{reason}")
            return
        freq.state = RequestState.QUEUED
        self.queue.insert(0, freq)  # stranded work resumes first (the
        # fleet analog of preemption's requeue-at-front)
        self.metrics.requests_requeued += 1

    def _fleet_release(self, freq: FleetRequest, state: RequestState,
                       reason: str) -> None:
        """THE one place a fleet request's terminal bookkeeping (state /
        reason / finish time / terminal counters) is written — the
        router-level mirror of ``Scheduler._release``; the dslint
        terminal-path rule enforces both."""
        freq.state = state
        freq.finish_reason = reason
        freq.finish_time = time.perf_counter()
        if self.journal is not None:
            # the verdict is durable before the caller can observe it:
            # recovery will never re-serve (or re-deliver) this request
            self.journal.append_terminal(freq.fid, state.value, reason)
        field = {RequestState.FINISHED: "requests_finished",
                 RequestState.FAILED: "requests_failed",
                 RequestState.TIMEOUT: "requests_timeout",
                 RequestState.CANCELLED: "requests_cancelled"}[state]
        setattr(self.metrics, field, getattr(self.metrics, field) + 1)

    # -- status (the /statusz fleet section + ds_report) ----------------

    def status(self) -> Dict[str, Any]:
        """Point-in-time fleet status: per-replica health/goodput rows
        plus the router's own counters. Safe to call from a scrape
        thread (reads snapshot copies, never iterates live state)."""
        goodput = sum(r.engine.metrics.goodput_tokens_per_sec
                      for r in self.replicas if r.alive)
        return {
            "replicas": [r.status_row() for r in self.replicas],
            "routing": self.cfg.routing,
            "disaggregated": bool(self.cfg.prefill_replicas),
            "prefill_replicas": list(self.cfg.prefill_replicas),
            "queue_depth": len(self.queue),
            "in_flight": len(self._placements),
            "draining": self._draining,
            "replicas_total": len(self.replicas),
            "replicas_active": sum(1 for r in self.replicas
                                   if r.alive and not r.retired),
            "replicas_retired": sum(1 for r in self.replicas
                                    if r.retired),
            "scale_in_pending": sorted(self._pending_scale_in),
            "fleet_goodput_tokens_per_sec": round(goodput, 2),
            "routed_by_replica": {self.replicas[i].name: n
                                  for i, n in
                                  sorted(snapshot_items(
                                      self.routed_by_replica))},
            "journal": None if self.journal is None
            else self.journal.status(),
            "counters": self.metrics.snapshot(),
        }

    def check_consistent(self) -> None:
        """Fleet-wide pool invariants: every replica's accounting is
        consistent — after a drain, zero referenced pages anywhere, dead
        or alive (the chaos-suite bar, fleet edition)."""
        for rep in self.replicas:
            rep.engine.block_pool.check_consistent()


def init_fleet(engine, n_replicas: int, serving_config=None,
               router_config: Optional[RouterConfig] = None,
               serving_configs: Optional[List[Any]] = None
               ) -> ServingRouter:
    """Build ``n_replicas`` ServingEngines over ONE shared
    :class:`InferenceEngine` (same params, per-replica KV pool /
    scheduler / metrics) and front them with a router — the in-process
    fleet shape tests and benches drive. ``serving_configs`` overrides
    the per-replica config list (e.g. smaller pools on prefill
    replicas)."""
    if serving_configs is not None and len(serving_configs) != n_replicas:
        raise ValueError("serving_configs must name every replica")
    engines = [ServingEngine(engine,
                             serving_configs[i] if serving_configs
                             else serving_config)
               for i in range(n_replicas)]
    router = ServingRouter(engines, config=router_config)
    # elastic scale-out beyond the constructed fleet spawns through this
    # (new replicas take the LAST config — the decode shape on a
    # disaggregated fleet, the uniform one otherwise); each fresh
    # ServingEngine compiles its OWN resident program once, so the
    # one-compile-per-replica invariant holds across scale events
    spawn_cfg = serving_configs[-1] if serving_configs else serving_config
    router.replica_factory = lambda: ServingEngine(engine, spawn_cfg)
    return router
