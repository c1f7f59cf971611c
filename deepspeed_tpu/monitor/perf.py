"""Performance accounting: compiled-program registry, recompile sentinel,
cost-model FLOPs/bytes, MFU/MBU, HBM watermarks, and the compile ledger with
the engine's set-up record.

PR 5 made *events* observable (spans, flight dumps, metrics registry); this
layer makes *performance claims* measurable and defensible:

- **Compiled-program registry + recompile sentinel** — every resident
  jitted program (serving decode / chunked prefill / bucketed prefill,
  the training step, dense ``generate``) registers an **argument
  fingerprint** (shapes / dtypes / statics). A later call whose
  fingerprint differs IS a recompile (XLA keys its cache on exactly these),
  so the sentinel diffs the fingerprints and raises a runtime alarm —
  a tracer event + a registry counter — **naming the offending argument**
  and how it changed. The serving layer's "ONE decode compile" invariant
  stops being a test-only assertion and becomes something a production
  run screams about.
- **Cost accounting** — the TRAIN step counts its own matrix work:
  :class:`StepCost` walks the jaxpr the step's lowering was made from (no
  second trace), a scan's body times its length, by ``ds.*`` scope, the
  replays apart; ``train_mfu`` divides it by the published step time. The
  serving programs and dense ``generate`` keep
  ``jitfn.lower(*args).cost_analysis()`` captured once per program (the
  lowering is cached by jax: no second trace, no XLA compile), with a
  hand-rolled transformer FLOPs estimate as the fallback where the backend
  has no cost model -- XLA counts a ``while`` body ONCE and a Pallas call 0,
  which is why the train step left it. With step wall times this yields
  **MFU** (training / prefill: compute-bound) and **MBU + tokens/sec/chip**
  (decode: bandwidth-bound).
- **Device memory watermarks** — ``device.memory_stats()`` live/peak HBM
  bytes, graceful no-op on backends (CPU) that expose none.
- **Compile ledger + set-up record** — one set of ``jax.monitoring``
  listeners a process sums what jax itself times of every compile (trace,
  lowering, backend compile or persistent-cache read, cache hits and
  misses) per function and per open set-up span; a registry row says
  whether its program came from the cache and what it cost, and
  :class:`SetupRecord` is the flat record an engine publishes as
  ``ds.setup``. A listener runs only where something compiles.

A ``ProgramRegistry`` is meant for hot paths: one dict-equality check
per dispatch (the fingerprints are small flat dicts of strings) and
nothing on the device. What that check costs a decode step on the chip
is not measured: no cell drives the serving path yet (ROADMAP W1), and
a CPU run gives no time.
"""

import contextlib
import hashlib
import re
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.logging import logger
from .registry import snapshot_items

# ---------------------------------------------------------------------------
# device capability table (per chip)
# ---------------------------------------------------------------------------

#: peak dense (bf16/fp16) FLOPs/s and peak HBM bandwidth (bytes/s) PER CHIP,
#: keyed by a substring of ``device.device_kind``. Longest key wins, so
#: "TPU v5 lite" matches before a hypothetical "TPU v5". Sources: published
#: per-chip specs (v5e aka "v5 lite": 197 bf16 TFLOPs, 819 GB/s).
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v2": (46e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def device_peaks(device_kind: Optional[str]
                 ) -> Tuple[Optional[float], Optional[float]]:
    """(peak_flops_per_s, peak_hbm_bytes_per_s) per chip for a
    ``device.device_kind`` string. A host platform (CPU) has no peak:
    (None, None), and utilization gauges are omitted rather than wrong. A
    TPU that is not in the table is an error, not a default — a utilization
    computed against a guessed or missing peak would read like a result."""
    if not device_kind:
        return (None, None)
    best = None
    for key, peaks in DEVICE_PEAKS.items():
        if key in device_kind and (best is None or len(key) > len(best[0])):
            best = (key, peaks)
    if best is None and device_kind.upper().startswith("TPU"):
        raise KeyError(
            f"no peak FLOP/s / HBM bandwidth known for device_kind "
            f"{device_kind!r}: add it to monitor/perf.py DEVICE_PEAKS with "
            f"its source")
    return best[1] if best else (None, None)


# ---------------------------------------------------------------------------
# argument fingerprints
# ---------------------------------------------------------------------------

def _leaf_spec(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    return repr(x)


def spec(x: Any) -> str:
    """One argument's fingerprint component: ``dtype[shape]`` for arrays,
    a leaf-spec summary for pytrees, ``repr`` for statics — exactly the
    properties jax keys its compilation cache on, so *fingerprint changed*
    ⟺ *this call retraced/recompiled*."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return _leaf_spec(x)
    if isinstance(x, (list, tuple, dict)) or hasattr(x, "__dataclass_fields__"):
        import jax

        leaves = jax.tree_util.tree_leaves(x)
        if leaves and any(hasattr(l, "shape") for l in leaves):
            specs = [_leaf_spec(l) for l in leaves]
            # collapse runs of identical leaves ("f32[64,64] x48") so big
            # pytrees fingerprint compactly AND compare fast
            out: List[str] = []
            run = 1
            for i in range(1, len(specs) + 1):
                if i < len(specs) and specs[i] == specs[i - 1]:
                    run += 1
                    continue
                out.append(specs[i - 1] if run == 1
                           else f"{specs[i - 1]} x{run}")
                run = 1
            return f"pytree[{len(specs)}: " + "; ".join(out) + "]"
    return repr(x)


def fingerprint(**args: Any) -> Dict[str, str]:
    """Named-argument fingerprint of one program call."""
    return {name: spec(v) for name, v in args.items()}


def fingerprint_diff(old: Dict[str, str], new: Dict[str, str]
                     ) -> Dict[str, Tuple[Optional[str], Optional[str]]]:
    """{arg: (before, after)} for every argument that changed (None =
    argument added/removed)."""
    out: Dict[str, Tuple[Optional[str], Optional[str]]] = {}
    for k in {**old, **new}:
        if old.get(k) != new.get(k):
            out[k] = (old.get(k), new.get(k))
    return out


# ---------------------------------------------------------------------------
# compiled-program registry + recompile sentinel
# ---------------------------------------------------------------------------

class CompiledProgram:
    """One resident jitted program's accounting record."""

    __slots__ = ("name", "fingerprint", "compiles", "calls", "recompiles",
                 "flops", "bytes_accessed", "cost_source", "cost_attempted",
                 "memory", "step_cost")

    def __init__(self, name: str):
        self.name = name
        self.fingerprint: Optional[Dict[str, str]] = None
        self.compiles = 0      # XLA compiles (trace-time counter hook)
        self.calls = 0         # dispatches observed
        self.recompiles = 0    # sentinel alarms: fingerprint changed
        self.flops: Optional[float] = None           # per call
        self.bytes_accessed: Optional[float] = None  # per call
        # "cost_model" | "estimate" | "jaxpr" (the train step: StepCost)
        self.cost_source: Optional[str] = None
        #: capture tried (even unsuccessfully): a backend with no cost
        #: model AND no fallback must pay the lowering walk once, not on
        #: every hot-path dispatch
        self.cost_attempted = False
        #: ``SetupRecord.COUNTS`` of a train step built ahead of its first
        #: call: what its remat policy keeps and what the compiled program
        #: occupies (``export.memory_line`` prints it under the row)
        self.memory: Optional[Dict[str, int]] = None
        #: the train step's :class:`StepCost` (``export.step_cost_line``)
        self.step_cost: Optional["StepCost"] = None

    @property
    def cost_pending(self) -> bool:
        return not self.cost_attempted

    @property
    def fingerprint_hash(self) -> Optional[str]:
        if self.fingerprint is None:
            return None
        blob = ";".join(f"{k}={v}" for k, v in
                        sorted(self.fingerprint.items()))
        return hashlib.sha1(blob.encode()).hexdigest()[:10]

    def row(self) -> Dict[str, Any]:
        return {"name": self.name, "fingerprint": self.fingerprint_hash,
                "compiles": self.compiles, "recompiles": self.recompiles,
                "calls": self.calls, "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "cost_source": self.cost_source, "memory": self.memory,
                "step_cost": self.step_cost.row() if self.step_cost else None}


#: every live ProgramRegistry in the process, for ``ds_report``'s resident
#: compiled-program table (weak: the report must never pin a dropped engine)
_live_lock = threading.Lock()
_live_registries: "weakref.WeakSet[ProgramRegistry]" = weakref.WeakSet()  # dslint: guarded-by=_live_lock


class ProgramRegistry:
    """Get-or-create registry of :class:`CompiledProgram` records with the
    recompile sentinel on :meth:`observe_call`."""

    def __init__(self, tracer=None, metrics=None, scope: str = ""):
        self.scope = scope
        self.tracer = tracer
        self.metrics = metrics  # MetricsRegistry for the alarm counters
        self._lock = threading.Lock()
        #: keys arrive at runtime (per-bucket programs) while /statusz
        #: reads off-thread; get-or-create and snapshots both lock (one
        #: uncontended acquire per dispatch — noise against the
        #: fingerprint compare the dispatch already pays)
        self.programs: Dict[str, CompiledProgram] = {}  # dslint: guarded-by=_lock
        #: what each program's compiles cost (listening from here on)
        self.ledger = compile_ledger()
        with _live_lock:
            _live_registries.add(self)

    def program(self, name: str) -> CompiledProgram:
        with self._lock:
            prog = self.programs.get(name)
            if prog is None:
                prog = self.programs[name] = CompiledProgram(name)
        return prog

    def note_compile(self, name: str) -> None:
        """Trace-time hook: call from inside the traced function body (it
        runs exactly once per XLA compile, the ``compile_counts``
        pattern)."""
        self.program(name).compiles += 1

    def observe_call(self, name: str, fp: Dict[str, str]
                     ) -> Optional[Dict[str, Tuple[Optional[str],
                                                   Optional[str]]]]:
        """Record one dispatch. First call registers the fingerprint; a
        later call with a DIFFERENT fingerprint is a recompile — the
        sentinel fires (tracer event + metrics counter + warning log)
        naming every argument whose spec changed, and returns the diff
        (None = fingerprint stable)."""
        prog = self.program(name)
        prog.calls += 1
        if prog.fingerprint is None:
            prog.fingerprint = fp
            return None
        if fp == prog.fingerprint:
            return None
        diff = fingerprint_diff(prog.fingerprint, fp)
        prog.fingerprint = fp
        prog.recompiles += 1
        offenders = sorted(diff)
        if self.metrics is not None:
            self.metrics.counter("recompiles", program=name).inc()
        if self.tracer is not None and getattr(self.tracer, "enabled", False):
            self.tracer.instant(
                "recompile", cat="perf",
                args={"program": name, "args": offenders,
                      "changed": {k: [diff[k][0], diff[k][1]]
                                  for k in offenders}})
        changes = "; ".join(f"{k}: {diff[k][0]} -> {diff[k][1]}"
                            for k in offenders)
        logger.warning(
            f"perf sentinel: program {self.scope + '/' if self.scope else ''}"
            f"{name} RECOMPILED (call {prog.calls}) — argument(s) changed: "
            f"{changes}. Resident programs are supposed to see one shape "
            f"forever; this compile stalls the serving/training loop.")
        return diff

    def set_cost(self, name: str, flops: Optional[float],
                 bytes_accessed: Optional[float], source: str) -> None:
        prog = self.program(name)
        prog.flops = flops
        prog.bytes_accessed = bytes_accessed
        prog.cost_source = source

    @property
    def recompile_total(self) -> int:
        # snapshot under the lock: the admin server's /statusz thread
        # reads this while the engine may be registering a program —
        # walking a live view across the insert raises RuntimeError
        with self._lock:
            progs = list(self.programs.values())
        return sum(p.recompiles for p in progs)

    def table(self) -> List[Dict[str, Any]]:
        # same law as recompile_total: /statusz calls this from the
        # admin thread while the engine registers the next bucket's
        # program — snapshot whole under the lock, then sort the copy
        with self._lock:
            items = list(self.programs.items())
        rows = []
        for name, prog in sorted(items):
            row = {**prog.row(), **self.ledger.program(name)}
            if self.scope:
                row["name"] = f"{self.scope}/{name}"
            rows.append(row)
        return rows


def live_program_table() -> List[Dict[str, Any]]:
    """The resident compiled-program table across every live registry in
    this process (what ``ds_report`` prints)."""
    with _live_lock:
        regs = list(_live_registries)
    rows: List[Dict[str, Any]] = []
    for reg in regs:
        rows.extend(reg.table())
    return sorted(rows, key=lambda r: r["name"])


# ---------------------------------------------------------------------------
# cost-model capture + hand-rolled transformer estimates
# ---------------------------------------------------------------------------

def cost_analysis_of(jitfn, *args) -> Optional[Dict[str, float]]:
    """``{"flops", "bytes_accessed"}`` from the XLA cost model of a jitted
    function's lowering, or None where the backend offers no cost model.

    ``jitfn.lower(*args)`` reuses jax's cached lowering for already-called
    shapes — no second trace of the Python body (trace-time counters like
    ``compile_counts`` stay untouched) and no XLA compile."""
    try:
        ca = jitfn.lower(*args).cost_analysis()
        if isinstance(ca, (list, tuple)):  # per-partition variants
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None
        flops = float(ca.get("flops", -1.0))
        if flops <= 0:
            return None
        out = {"flops": flops}
        if ca.get("bytes accessed", 0):
            out["bytes_accessed"] = float(ca["bytes accessed"])
        return out
    except Exception as e:  # no cost model is a degraded mode, not an error
        logger.debug(f"perf: cost_analysis unavailable: "
                     f"{type(e).__name__}: {e}")
        return None


def transformer_flops_per_token(cfg, context_len: int) -> float:
    """Hand-rolled dense-transformer FLOPs for ONE decoded token against a
    ``context_len``-wide KV context (the fallback when the backend has no
    cost model). Counts matmuls at 2·M·N·K: qkv/o projections, the
    (gate/up/down when ``intermediate_size`` differs, else 2-matmul) MLP,
    QKᵀ + AV attention over ``context_len`` keys, and the LM head.
    Embedding gathers are free."""
    L = int(getattr(cfg, "num_hidden_layers", getattr(cfg, "n_layer", 0)))
    h = int(getattr(cfg, "hidden_size", getattr(cfg, "n_embd", 0)))
    H = int(getattr(cfg, "num_attention_heads", getattr(cfg, "n_head", 1)))
    Hkv = int(getattr(cfg, "num_key_value_heads", H) or H)
    D = int(getattr(cfg, "head_dim", max(1, h // max(1, H))))
    V = int(getattr(cfg, "vocab_size", 0))
    inter = getattr(cfg, "intermediate_size", None)
    if inter:  # llama-family: gate + up + down
        mlp = 2 * h * int(inter) * 3
    else:      # gpt2-family: fc(4h) + proj
        mlp = 2 * h * (4 * h) * 2
    qkv = 2 * h * (H * D + 2 * Hkv * D)
    o = 2 * (H * D) * h
    attn = 2 * 2 * H * D * int(context_len)
    return float(L * (qkv + o + mlp + attn) + 2 * h * V)


def estimate_decode_step_flops(cfg, batch: int, context_len: int) -> float:
    """Fallback FLOPs of one resident decode step: the program computes
    every one of its ``batch`` slots (padding included — that IS the
    hardware work) against a ``context_len``-deep context."""
    return batch * transformer_flops_per_token(cfg, context_len)


def param_bytes(params) -> int:
    import jax

    return sum(int(getattr(l, "nbytes", 0) or 0)
               for l in jax.tree_util.tree_leaves(params))


def estimate_decode_step_bytes(cfg, batch: int, context_len: int,
                               params_nbytes: int,
                               kv_bytes_per_elem: int = 2) -> float:
    """Fallback bytes-accessed of one decode step: weights streamed once
    plus the KV context read per slot — decode's two bandwidth sinks."""
    L = int(getattr(cfg, "num_hidden_layers", getattr(cfg, "n_layer", 0)))
    H = int(getattr(cfg, "num_attention_heads", getattr(cfg, "n_head", 1)))
    Hkv = int(getattr(cfg, "num_key_value_heads", H) or H)
    h = int(getattr(cfg, "hidden_size", getattr(cfg, "n_embd", 0)))
    D = int(getattr(cfg, "head_dim", max(1, h // max(1, H))))
    kv = batch * L * 2 * Hkv * D * int(context_len) * kv_bytes_per_elem
    return float(params_nbytes + kv)


# ---------------------------------------------------------------------------
# device memory watermarks
# ---------------------------------------------------------------------------

_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_alloc_size")


def device_memory_stats() -> List[Dict[str, Any]]:
    """Live/peak HBM per local device, ``[]`` where the backend exposes no
    allocator stats (CPU) — watermark consumers degrade to absent fields,
    never fake zeros."""
    import jax

    out = []
    try:
        devices = jax.local_devices()
    except Exception:
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        rec: Dict[str, Any] = {"device": str(d.id),
                               "kind": getattr(d, "device_kind", "?")}
        for k in _MEM_KEYS:
            if k in stats:
                rec[k] = int(stats[k])
        out.append(rec)
    return out


def hbm_watermarks() -> Tuple[Optional[int], Optional[int]]:
    """(bytes_in_use, peak_bytes_in_use) summed over local devices; (None,
    None) on backends without allocator stats."""
    stats = device_memory_stats()
    if not stats:
        return (None, None)
    return (sum(s.get("bytes_in_use", 0) for s in stats),
            sum(s.get("peak_bytes_in_use", 0) for s in stats))


# ---------------------------------------------------------------------------
# compile ledger: what jax says each compile cost, and the set-up record
# ---------------------------------------------------------------------------

#: ``jax.monitoring`` duration events -> the ledger's column of seconds.
#: jax fires them only where something traces, lowers, compiles or reads
#: the persistent cache: never in a warm step.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_DURATION_EVENTS = {
    _TRACE_EVENT: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    # compile_or_get_cached as a whole: a cache read is inside it
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
#: counting events -> the ledger's column of counts (a miss is counted
#: where jax WRITES the entry: a compile under
#: ``jax_persistent_cache_min_compile_time_secs`` is neither)
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
#: the columns a set-up record carries, inside the program's spans and
#: ``outside`` them; ``programs`` counts backend compiles-or-reads
LEDGER_COLUMNS = ("trace_s", "lower_s", "backend_s", "cache_read_s",
                  "cache_hits", "cache_misses", "programs")
_CACHE_COLUMNS = ("cache_read_s", "cache_hits", "cache_misses")
_FUN_WRAPPED = re.compile(r"(?:jit|pmap)\((.*)\)")
_FUN_AFFIXES = re.compile(r"^(ds_)?|(_n\d+)?$")


def _fun_key(fun_name: str) -> str:
    """``jit(ds_train_step_n3)`` (what jax calls the lowered and compiled
    module), ``ds_train_step_n3`` (the traced function) and the registry's
    ``train_step`` are one program: the key drops jax's wrapper, the
    ``ds_`` of a named step and ``tracing.versioned``'s suffix; a bucketed
    program's ``[width]`` goes too, so the buckets of one function share
    its row."""
    wrapped = _FUN_WRAPPED.fullmatch(fun_name)
    name = wrapped.group(1) if wrapped else fun_name
    return _FUN_AFFIXES.sub("", re.sub(r"\[.*\]$", "", name))


class CompileLedger:
    """Sums of what ``jax.monitoring`` reports about compiles, per function
    and per open set-up span. One a process (:func:`compile_ledger`): jax's
    listeners are process-wide and cannot be taken back one by one.

    An event is charged to the innermost set-up span open on its thread
    (:meth:`charging` names it in a thread-local) or, with none
    open, to ``outside`` — a benchmark's reference program compiles in the
    engine's process and must not read as the engine's. The cache's events
    carry no function name; they fire inside ``backend_compile_duration``,
    which does, so they wait in the thread-local until it closes. Traces
    nest (a jitted step traces every jitted function it calls, and each
    reports its own, inclusive, duration): a function's row keeps its
    inclusive time, a span's sum takes the outermost traces alone — jax
    reports a trace's start as a scalar, which is how the depth is known."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        #: listener invocations and the seconds spent inside them: what
        #: the instrumentation costs, and a test's proof that a warm call
        #: fires none
        self.calls = 0  # dslint: guarded-by=_lock
        self.listener_s = 0.0  # dslint: guarded-by=_lock
        self.by_fun: Dict[str, Dict[str, float]] = {}  # dslint: guarded-by=_lock
        self.outside: Dict[str, float] = {}  # dslint: guarded-by=_lock

    def listen(self) -> "CompileLedger":
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_scalar_listener(self._on_scalar)
        return self

    @contextlib.contextmanager
    def charging(self, sums: Dict[str, Dict[str, float]], span: str):
        """While open, this thread's compiles go to ``sums[span]``; the
        span that was charged before comes back after."""
        tls = self._tls
        before = getattr(tls, "charge", None)
        tls.charge = (sums, span)
        try:
            yield
        finally:
            tls.charge = before

    def _on_scalar(self, event: str, value: float, **kw) -> None:
        # a trace starts: one deeper (the other starts are not needed)
        tls = self._tls
        if event == _TRACE_EVENT:
            tls.depth = getattr(tls, "depth", 0) + 1
        self._charge(None, 0, None)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        self._charge(_DURATION_EVENTS.get(event), duration,
                     kw.get("fun_name"))

    def _on_event(self, event: str, **kw) -> None:
        self._charge(_COUNT_EVENTS.get(event), 1, None)

    def _charge(self, column: Optional[str], value: float,
                fun_name: Optional[str]) -> None:
        t0 = time.perf_counter()
        tls = self._tls
        with self._lock:
            self.calls += 1
            nested = False
            if column == "trace_s":
                # a trace that began before the ledger listened has no
                # start on record: never below the outermost
                tls.depth = max(getattr(tls, "depth", 0) - 1, 0)
                nested = tls.depth > 0
            if column is not None:
                sums, span = getattr(tls, "charge", None) or (None, None)
                row = self.outside if sums is None \
                    else sums.setdefault(span, {})
                if not nested:
                    row[column] = row.get(column, 0) + value
                pending = tls.__dict__.setdefault("pending", {})
                if fun_name is None:
                    pending[column] = pending.get(column, 0) + value
                else:
                    fun = self.by_fun.setdefault(_fun_key(fun_name), {})
                    fun[column] = fun.get(column, 0) + value
                    if column == "backend_s":
                        row["programs"] = row.get("programs", 0) + 1
                        fun["programs"] = fun.get("programs", 0) + 1
                        for k in _CACHE_COLUMNS:
                            if k in pending:
                                fun[k] = fun.get(k, 0) + pending.pop(k)
            self.listener_s += time.perf_counter() - t0

    def program(self, name: str) -> Dict[str, Any]:
        """What the ledger knows of the registry's program ``name``:
        seconds traced, lowered and in the backend (compile or cache
        read), and whether every one of its compiles came from the
        persistent cache (None: it never asked the cache, or never
        compiled since the ledger listens)."""
        with self._lock:
            fun = dict(self.by_fun.get(_fun_key(name), {}))
        asked = fun.get("cache_hits", 0) + fun.get("cache_misses", 0)
        return {"trace_s": fun.get("trace_s"), "lower_s": fun.get("lower_s"),
                "backend_s": fun.get("backend_s"),
                "cache_hit": fun.get("cache_misses", 0) == 0
                if asked else None}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"calls": self.calls, "listener_s": self.listener_s,
                    "outside": dict(self.outside),
                    "by_fun": {k: dict(v) for k, v in self.by_fun.items()}}


_ledger_lock = threading.Lock()
_ledger: Optional[CompileLedger] = None  # dslint: guarded-by=_ledger_lock


def compile_ledger() -> CompileLedger:
    """The process's ledger; the first call registers its listeners."""
    global _ledger
    with _ledger_lock:
        if _ledger is None:
            _ledger = CompileLedger().listen()
        return _ledger


class SetupRecord:
    """One engine's set-up as flat numbers: the seconds of each named part
    and the ledger's sums under each of its set-up spans. Kept whether or
    not the tracer's ring is enabled: a span reads the clock twice anyway
    (``tracing._Span.seconds``). Set-up ends where the first step returns
    (:meth:`close`): from there on the record stands — a recompile the
    sentinel flags, or something the caller compiles hundreds of steps
    later, is not set-up."""

    PARTS = ("import", "pre_init", "init", "init_shapes", "init_params",
             "init_opt_state", "init_step", "first_step", "first_dispatch",
             "first_wait", "cost_capture")
    #: what the engine's ahead-of-time build of the train step found
    #: (``runtime/engine.py _fit_train_step``): the bytes and the count of
    #: the named values its remat policy keeps beyond the policy's own, the
    #: budget they were chosen under, how often the compiled step made the
    #: engine take a choice back, and ``memory_analysis()`` of the compiled
    #: step (the peak: arguments included; 0 where the backend has none)
    COUNTS = ("remat_kept_bytes", "remat_kept_names", "remat_room_bytes",
              "remat_fallbacks", "step_argument_bytes", "step_temp_bytes",
              "step_peak_bytes")

    def __init__(self):
        self.ledger = compile_ledger()
        self.counts: Dict[str, int] = dict.fromkeys(self.COUNTS, 0)
        self.seconds: Dict[str, float] = {}
        self.sums: Dict[str, Dict[str, float]] = {}
        self._closed: Optional[Dict[str, float]] = None

    @contextlib.contextmanager
    def span(self, span, part: Optional[str] = None):
        """Run ``span`` (an unopened ``Tracer.span``) as a set-up span:
        compiles on this thread are charged to its name while it is the
        innermost, and its seconds become the record's ``part`` (its own
        name unless given). After :meth:`close` it is the span alone."""
        if self._closed is not None:
            with span:
                yield span
            return
        with span, self.ledger.charging(self.sums, span.name):
            yield span
        self.seconds[part or span.name] = span.seconds

    @property
    def first_step_done(self) -> bool:
        return "first_step" in self.seconds

    def close(self) -> None:
        self._closed = self._numbers()

    def _numbers(self) -> Dict[str, float]:
        out = {f"{k}_s": self.seconds.get(k, 0.0) for k in self.PARTS}
        out.update(self.counts)
        outside = self.ledger.snapshot()["outside"]
        for k in LEDGER_COLUMNS:
            out[k] = sum(row.get(k, 0) for row in self.sums.values())
        for k in LEDGER_COLUMNS:
            out[f"outside_{k}"] = outside.get(k, 0)
        return out

    def record(self, steps_before: int) -> Dict[str, float]:
        """The flat record (docs/observability.md has each key): parts the
        engine never ran read 0; ``outside_*`` is the process's sum where
        set-up ended."""
        numbers = self._numbers() if self._closed is None else self._closed
        return {**numbers, "steps_before": steps_before}


class StepCost:
    """The matrix operations ONE train step runs, counted by the program
    from the jaxpr its own lowering came from
    (``profiling/flops_profiler.walk_jaxpr``, walked once in set-up inside
    ``cost_capture``): ``2 x`` the multiply-accumulates of every plain,
    grouped and convolutional product and of the Pallas kernels in
    ``ops/pallas MATMUL_FLOPS``, a scan's body times its length, GLOBAL (over
    every device), by the innermost ``ds.`` scope of the name stack -- the
    scope ``benchmark/scope_reduce`` gives a device operation's time to --
    and apart for the forward pass, the backward pass and what
    ``jax.checkpoint`` REPLAYS. XLA's ``cost_analysis()`` cannot stand here:
    it counts a ``while`` body once, so a scanned stack of any depth reads as
    one layer, and a Pallas call reads 0. Published like the set-up record:
    the train step's registry row and the ``ds.step_cost`` host event."""

    def __init__(self, walk, walk_s: float):
        self.scopes: Dict[str, Dict[str, int]] = {
            scope: dict(row) for scope, row in sorted(walk.scopes.items())}
        self.cond_spread_flops = int(walk.cond_spread_flops)
        #: what counted 0, by name, with its calls a step: a Pallas kernel
        #: without an entry in ``MATMUL_FLOPS`` (or with a traced grid), a
        #: ``while``
        self.uncounted: Dict[str, int] = dict(sorted(walk.uncounted.items()))
        self.walk_s = walk_s

    @property
    def matmul_flops(self) -> int:
        return sum(sum(row.values()) for row in self.scopes.values())

    @property
    def replayed_flops(self) -> int:
        return sum(row["replayed"] for row in self.scopes.values())

    @property
    def model_flops(self) -> int:
        """What the model asks for: the replays left out (``train_mfu``)."""
        return self.matmul_flops - self.replayed_flops

    @staticmethod
    def stat(scope: str) -> str:
        """``ds.mlp`` -> ``mlp``, ``(unscoped)`` -> ``unscoped``: a scope as
        a stat's (and a gauge's) suffix."""
        return re.sub(r"^ds\.|[()]", "", scope)

    def record(self) -> Dict[str, float]:
        """One stat a number (docs/observability.md has each): a step's
        matrix operations a scope (forward + backward + replayed), the
        replayed part of them, the totals, the spread a ``cond``'s larger
        branch would add, the calls of kernels the count leaves out (in all
        and a kernel), and the seconds the walk took."""
        out: Dict[str, float] = {}
        for scope, row in self.scopes.items():
            out[f"matmul_flops_{self.stat(scope)}"] = sum(row.values())
            out[f"replayed_flops_{self.stat(scope)}"] = row["replayed"]
        out.update(matmul_flops=self.matmul_flops,
                   replayed_flops=self.replayed_flops,
                   cond_spread_flops=self.cond_spread_flops,
                   uncounted_kernel_calls=sum(
                       n for k, n in self.uncounted.items() if k != "while"),
                   walk_s=self.walk_s)
        for name, calls in self.uncounted.items():
            out[f"uncounted_{name}"] = calls
        return out

    def row(self) -> Dict[str, Any]:
        return {**self.record(), "scopes": self.scopes,
                "uncounted": self.uncounted}


# ---------------------------------------------------------------------------
# PerfAccounting: the engine-side bundle
# ---------------------------------------------------------------------------

class PerfAccounting:
    """Everything one engine needs, bundled: a scoped
    :class:`ProgramRegistry`, the device's peak table, per-program
    utilization math, cached pytree fingerprints for stable-identity args
    (params), and watermark sampling with the backend capability probed
    once."""

    def __init__(self, tracer=None, metrics=None, scope: str = "",
                 n_devices: int = 1, device_kind: Optional[str] = None):
        if device_kind is None:
            try:
                import jax

                device_kind = jax.devices()[0].device_kind
            except Exception:
                device_kind = None
        self.device_kind = device_kind
        self.n_devices = max(1, int(n_devices))
        self.peak_flops, self.peak_hbm_bw = device_peaks(device_kind)
        self.programs = ProgramRegistry(tracer=tracer, metrics=metrics,
                                        scope=scope)
        self._spec_memo: Dict[str, Tuple[int, str]] = {}
        #: per-step utilization entries keyed by program name — keys
        #: arrive at runtime and /statusz reads off-thread (list() law)
        self.last: Dict[str, Dict[str, Optional[float]]] = {}  # dslint: guarded-by=snapshot
        #: None = unprobed, False = backend has no allocator stats
        self._mem_capable: Optional[bool] = None

    # -- fingerprints ---------------------------------------------------

    def cached_spec(self, key: str, tree: Any) -> str:
        """Pytree spec memoized on object identity — params keep one
        object across a run, so the per-call cost is one ``id()``
        compare instead of an O(leaves) walk."""
        memo = self._spec_memo.get(key)
        if memo is not None and memo[0] == id(tree):
            return memo[1]
        s = spec(tree)
        self._spec_memo[key] = (id(tree), s)
        return s

    def observe_call(self, name: str, **args: Any):
        return self.programs.observe_call(name, fingerprint(**args))

    def note_compile(self, name: str) -> None:
        self.programs.note_compile(name)

    # -- cost capture ---------------------------------------------------

    def capture_cost(self, name: str, jitfn, args: Tuple[Any, ...],
                     fallback: Optional[Callable[[], Optional[Dict[str, float]]]]
                     = None) -> None:
        """Capture a program's per-call FLOPs / bytes-accessed, once: XLA
        cost model first, the hand-rolled estimate as fallback. Never
        raises — accounting must not take down the engine it measures.
        A FAILED capture is latched too (``cost_attempted``): retrying
        the lowering walk per dispatch would tax exactly the hot path
        this layer promises not to."""
        prog = self.programs.program(name)
        if prog.cost_attempted:
            return
        prog.cost_attempted = True
        cost = cost_analysis_of(jitfn, *args)
        source = "cost_model"
        if cost is None and fallback is not None:
            try:
                cost = fallback()
            except Exception as e:
                logger.debug(f"perf: flops fallback for {name} failed: {e}")
                cost = None
            source = "estimate"
        if cost is None:
            return
        self.programs.set_cost(name, cost.get("flops"),
                               cost.get("bytes_accessed"), source)

    def capture_step_cost(self, name: str, jaxpr) -> Optional[StepCost]:
        """The TRAIN step's cost, once: a walk of the jaxpr its lowering
        was made from (no second trace, no executable; ``cost_analysis()``
        is not consulted -- :class:`StepCost` says why). ``flops`` of the
        row are the operations the model asks for, ``cost_source``
        ``"jaxpr"``; the step publishes no bytes. Latched and never raising,
        like :meth:`capture_cost`."""
        prog = self.programs.program(name)
        if prog.cost_attempted:
            return prog.step_cost
        prog.cost_attempted = True      # with no jaxpr too: the step is warm
        if jaxpr is None:
            return None
        try:
            from ..profiling.flops_profiler.profiler import walk_jaxpr

            t0 = time.perf_counter()
            walk = walk_jaxpr(jaxpr)
            prog.step_cost = StepCost(walk, time.perf_counter() - t0)
        except Exception as e:
            logger.warning(f"perf: the walk of {name}'s jaxpr failed: "
                           f"{type(e).__name__}: {e}")
            return None
        self.programs.set_cost(name, float(prog.step_cost.model_flops), None,
                               "jaxpr")
        return prog.step_cost

    # -- utilization ----------------------------------------------------

    def on_program_step(self, name: str, dt_s: float,
                        tokens: Optional[int] = None
                        ) -> Dict[str, Optional[float]]:
        """Fold one timed dispatch of ``name`` into utilization gauges:
        MFU = flops / (dt · peak_flops · chips), MBU = bytes / (dt ·
        peak_bw · chips); both None until the cost is captured or where
        the device peak is unknown (CPU). ``tokens`` adds
        tokens/sec/chip."""
        prog = self.programs.programs.get(name)
        vals: Dict[str, Optional[float]] = {
            "flops_per_step": prog.flops if prog else None,
            "bytes_per_step": prog.bytes_accessed if prog else None,
            "mfu": None, "mbu": None, "flops_per_sec": None,
            "tokens_per_sec_per_chip": None,
        }
        if dt_s > 0 and prog is not None:
            if prog.flops:
                vals["flops_per_sec"] = prog.flops / dt_s
                if self.peak_flops:
                    vals["mfu"] = prog.flops / (
                        dt_s * self.peak_flops * self.n_devices)
            if prog.bytes_accessed and self.peak_hbm_bw:
                vals["mbu"] = prog.bytes_accessed / (
                    dt_s * self.peak_hbm_bw * self.n_devices)
            if tokens is not None:
                vals["tokens_per_sec_per_chip"] = tokens / (
                    dt_s * self.n_devices)
        self.last[name] = vals
        return vals

    # -- watermarks -----------------------------------------------------

    def memory_watermarks(self) -> Tuple[Optional[int], Optional[int]]:
        """(live, peak) HBM bytes; one capability probe, then a cheap
        no-op forever on backends (CPU) without allocator stats."""
        if self._mem_capable is False:
            return (None, None)
        live, peak = hbm_watermarks()
        if self._mem_capable is None:
            self._mem_capable = live is not None
        return (live, peak)

    # -- reporting ------------------------------------------------------

    @property
    def recompile_total(self) -> int:
        return self.programs.recompile_total

    def summary(self) -> Dict[str, Any]:
        """One JSON-able block for CLI reports and bench artifacts."""
        live, peak = self.memory_watermarks()
        return {
            "device_kind": self.device_kind,
            "n_devices": self.n_devices,
            "peak_flops_per_chip": self.peak_flops,
            "peak_hbm_bytes_per_s_per_chip": self.peak_hbm_bw,
            "hbm_bytes_in_use": live,
            "hbm_peak_bytes": peak,
            "programs": self.programs.table(),
            # whole-snapshot first — /statusz reads this off-thread
            # while the engine publishes per-step utilization entries
            "utilization": {k: dict(v)
                            for k, v in snapshot_items(self.last)},
        }
