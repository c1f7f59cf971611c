"""Export / control plane: Prometheus rendering + the admin HTTP server.

Everything observability built so far (PR 5 tracer, PR 6 perf registry)
is in-process pull — nothing OUTSIDE the Python process can ask "are you
healthy, what's your KV headroom, are you meeting SLO?". This module is
the boundary every replica of a future fleet speaks:

- :func:`render_prometheus` — Prometheus text exposition (format 0.0.4)
  over the existing :class:`~..registry.MetricsRegistry`:
  Counter → ``counter``, Gauge → ``gauge``, Histogram → ``summary`` with
  quantile legs, labels preserved, everything under the snake_case
  ``ds_`` namespace. Plain scalar snapshots (``ServingMetrics.snapshot``)
  render as gauges through the same call.
- :class:`AdminServer` — a tiny stdlib ``ThreadingHTTPServer`` on a
  daemon thread with the endpoints a serving router health-checks:

  ========== =============================================================
  /metrics   Prometheus text (always 200 while the process lives — the
             scrape must keep working even when the engine is unhealthy)
  /healthz   liveness: 200 while the engine can make progress; 503 while
             a watchdog-abandoned step is still wedged in device compute
  /readyz    readiness: 200 only when admission is open (not draining),
             KV headroom is above the brownout line, and the resident
             program is compiled; 503 with the failing bits otherwise
  /statusz   human-readable status page: resident compiled-program table,
             recompile counts, HBM watermarks, metrics snapshot
  /profilez  ``?seconds=N``: on-demand ``jax.profiler`` capture into the
             trace dir (one at a time — a second request gets 409)
  ========== =============================================================

  Endpoint callbacks are injected, so the server is engine-agnostic and
  can bind BEFORE the model loads (a router sees liveness during the
  multi-minute checkpoint load); :func:`attach_serving_engine` wires a
  live :class:`ServingEngine` in afterwards. A callback that raises
  returns 500 with the error text — a broken status page must never take
  down the server (or the engine behind it).

Status codes are a CONTRACT (docs/observability.md "Control plane"):
routers may key on 200-vs-503 for /healthz and /readyz; bodies are JSON
detail for humans and dashboards, never part of the routing contract.
"""

import json
import os
import threading
import time
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..utils.logging import log_dist, logger
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       snapshot_items)

#: quantile legs a Histogram renders as a Prometheus summary
SUMMARY_QUANTILES = (0.5, 0.95, 0.99)

#: exposition content type (text format 0.0.4 — what every scraper speaks)
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def _sanitize_name(name: str) -> str:
    """Metric names must match ``[a-zA-Z_:][a-zA-Z0-9_:]*``."""
    out = "".join(c if c.isalnum() or c in "_:" else "_" for c in name)
    if out and out[0].isdigit():
        out = "_" + out
    return out or "_"


def _escape_label(value: str) -> str:
    """Label-value escaping per the exposition format."""
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def split_key(key: str) -> Tuple[str, Dict[str, str]]:
    """Split a registry key (``name{k=v,k2=v2}`` — the ``_key`` format of
    ``monitor/registry.py``) back into ``(name, labels)``."""
    if "{" not in key or not key.endswith("}"):
        return key, {}
    name, inner = key[:-1].split("{", 1)
    labels: Dict[str, str] = {}
    for part in inner.split(","):
        if "=" in part:
            k, v = part.split("=", 1)
            labels[k] = v
    return name, labels


def parse_prometheus(text: str) -> Tuple[Dict[Tuple[str, frozenset], float],
                                         Dict[str, str]]:
    """Scrape-side inverse of :func:`render_prometheus`: returns
    ``({(metric_name, frozenset(labels.items())): value},
    {family: type})``. For tests and in-process tooling that read a
    replica's /metrics — a real fleet points an actual Prometheus at
    it. Raises ValueError on a malformed exposition line."""
    import re

    series: Dict[Tuple[str, frozenset], float] = {}
    types: Dict[str, str] = {}
    # the label blob is matched GREEDILY to the last '}' before the value
    # ('\{[^}]*\}' would stop at a '}' INSIDE a quoted label value, which
    # the exposition format allows unescaped); the value is \S+ at end of
    # line, so greed cannot overrun
    line_re = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)$")
    label_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            if len(parts) == 4:
                types[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue
        m = line_re.match(line)
        if m is None:
            raise ValueError(f"malformed exposition line: {line!r}")
        name, blob, value = m.groups()
        labels = {}
        for k, v in label_re.findall(blob or ""):
            # single-pass unescape: chained str.replace corrupts an
            # escaped backslash followed by 'n' ("C:\\new" -> "C:\<LF>ew")
            labels[k] = re.sub(
                r"\\(.)", lambda mm: {"n": "\n"}.get(mm.group(1),
                                                     mm.group(1)), v)
        series[(name, frozenset(labels.items()))] = float(value)
    return series, types


def _labels_text(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{_sanitize_name(k)}="{_escape_label(str(v))}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt(v: float) -> str:
    if v != v:  # NaN
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    return repr(float(v))


def render_prometheus(registry: Optional[MetricsRegistry] = None,
                      scalars: Optional[Dict[str, float]] = None,
                      namespace: str = "ds") -> str:
    """Prometheus text exposition of a metrics registry and/or a flat
    scalar snapshot.

    Registry metrics keep their kind (Counter → ``counter``, Gauge →
    ``gauge``, Histogram → ``summary`` with p50/p95/p99 quantile legs +
    ``_sum``/``_count``); ``scalars`` (e.g. ``ServingMetrics.snapshot()``)
    render as gauges. Keys in either source may carry the registry's
    ``name{k=v}`` label format — labels are preserved into the exposition.
    Output is sorted and stable, one ``# TYPE`` line per metric family.
    """
    # family -> (kind, [lines]); grouped so every family gets exactly one
    # TYPE header even when labeled series split across registry keys
    families: Dict[str, Tuple[str, List[str]]] = {}

    def fam(name: str, kind: str) -> List[str]:
        ent = families.get(name)
        if ent is None:
            ent = families[name] = (kind, [])
        elif ent[0] != kind:
            # one family, two kinds (e.g. a scalar snapshot key colliding
            # with a registry histogram name): scrapers reject duplicate
            # TYPE headers, so the first kind wins — but silently filing
            # a gauge under a summary header would corrupt the family, so
            # say so
            logger.warning(f"prometheus render: metric family {name!r} "
                           f"exposed as both {ent[0]} and {kind}; keeping "
                           f"{ent[0]} (rename one source)")
        return ent[1]

    ns = (namespace + "_") if namespace else ""
    if registry is not None:
        for key, metric in registry.items():
            name, labels = split_key(key)
            mname = ns + _sanitize_name(name)
            if isinstance(metric, Counter):
                fam(mname, "counter").append(
                    f"{mname}{_labels_text(labels)} {_fmt(metric.value)}")
            elif isinstance(metric, Gauge):
                fam(mname, "gauge").append(
                    f"{mname}{_labels_text(labels)} {_fmt(metric.value)}")
            elif isinstance(metric, Histogram):
                lines = fam(mname, "summary")
                for q in SUMMARY_QUANTILES:
                    p = metric.percentile(q)
                    if p is None:
                        continue
                    lines.append(
                        f"{mname}{_labels_text({**labels, 'quantile': str(q)})}"
                        f" {_fmt(p)}")
                lines.append(f"{mname}_sum{_labels_text(labels)} "
                             f"{_fmt(metric.sum)}")
                lines.append(f"{mname}_count{_labels_text(labels)} "
                             f"{_fmt(float(metric.count))}")
    for key, value in (scalars or {}).items():
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        name, labels = split_key(key)
        mname = ns + _sanitize_name(name)
        fam(mname, "gauge").append(
            f"{mname}{_labels_text(labels)} {_fmt(float(value))}")

    out: List[str] = []
    for name in sorted(families):
        kind, lines = families[name]
        out.append(f"# TYPE {name} {kind}")
        out.extend(sorted(lines))
    return "\n".join(out) + ("\n" if out else "")


# ---------------------------------------------------------------------------
# the admin server
# ---------------------------------------------------------------------------

#: every live AdminServer in the process, for ``ds_report`` (weak refs: a
#: status report must never pin a closed server or its engine)
_live_lock = threading.Lock()
_live_servers: "weakref.WeakSet[AdminServer]" = weakref.WeakSet()  # dslint: guarded-by=_live_lock


def live_admin_servers() -> List["AdminServer"]:
    with _live_lock:
        return [s for s in _live_servers if s.is_alive]


def _default_profile(seconds: float, out_dir: str) -> str:
    """On-demand ``jax.profiler`` capture (the /profilez backend)."""
    import jax

    path = os.path.join(out_dir,
                        f"profile_{time.strftime('%Y%m%d-%H%M%S')}")
    jax.profiler.start_trace(path)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    return path


class AdminServer:
    """Admin/control-plane HTTP server on a daemon thread.

    Endpoint behavior is injected via callables so the server can exist
    before (and independent of) any engine:

    - ``metrics_fn() -> str`` — the /metrics body (Prometheus text);
    - ``health_fn() -> (ok, detail_dict)`` — /healthz (503 when not ok);
    - ``ready_fn() -> (ok, detail_dict)`` — /readyz (503 when not ok);
    - ``status_fn() -> str`` — the human-readable /statusz page;
    - ``profile_dir`` + ``profile_fn(seconds, dir) -> path`` — /profilez
      (absent profile_dir ⇒ 501; concurrent captures ⇒ 409).

    ``port=0`` binds an ephemeral port (read :attr:`port` after
    construction — what the tests do); the conventional "admin disabled"
    knob (``ds_serve --admin-port 0``) lives at the CLI layer, which
    simply never constructs a server.
    """

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 metrics_fn: Optional[Callable[[], str]] = None,
                 health_fn: Optional[Callable[[], Tuple[bool, Dict]]] = None,
                 ready_fn: Optional[Callable[[], Tuple[bool, Dict]]] = None,
                 status_fn: Optional[Callable[[], str]] = None,
                 profile_dir: Optional[str] = None,
                 profile_fn: Optional[Callable[[float, str], str]] = None,
                 max_profile_seconds: float = 60.0):
        self.metrics_fn = metrics_fn
        self.health_fn = health_fn
        self.ready_fn = ready_fn
        self.status_fn = status_fn
        self.profile_dir = profile_dir
        self.profile_fn = profile_fn or _default_profile
        self.max_profile_seconds = max_profile_seconds
        #: one capture at a time: concurrent jax.profiler traces clobber
        #: each other (and double the overhead the capture measures)
        self._profile_latch = threading.Lock()
        #: wall time of the last successful /metrics scrape (None = never
        #: scraped) — surfaced by ds_report's admin-endpoint status
        self.last_scrape_time: Optional[float] = None
        self.scrape_count = 0

        admin = self  # the handler class closes over the server instance

        class Handler(BaseHTTPRequestHandler):
            # stdlib logs every request to stderr by default; the admin
            # plane must stay silent under a 1/s scrape interval
            def log_message(self, fmt, *args):  # noqa: N802
                pass

            def do_GET(self):  # noqa: N802
                try:
                    admin._route(self)
                except BrokenPipeError:
                    pass  # scraper hung up mid-response
                except Exception as e:  # never take the server down
                    try:
                        admin._send(self, 500, "text/plain",
                                    f"admin endpoint error: "
                                    f"{type(e).__name__}: {e}\n")
                    except Exception:
                        pass

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name=f"ds-admin-{self.port}",
                                        daemon=True)
        self._thread.start()
        with _live_lock:
            _live_servers.add(self)
        log_dist(f"admin server: listening on http://{host}:{self.port} "
                 f"(/metrics /healthz /readyz /statusz /profilez)",
                 ranks=[0])

    # -- wiring --------------------------------------------------------

    @property
    def is_alive(self) -> bool:
        return self._thread.is_alive()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()

    # -- request handling ----------------------------------------------

    def _send(self, handler, code: int, ctype: str, body: str) -> None:
        data = body.encode("utf-8")
        handler.send_response(code)
        handler.send_header("Content-Type", ctype)
        handler.send_header("Content-Length", str(len(data)))
        handler.end_headers()
        handler.wfile.write(data)

    def _send_probe(self, handler, ok: bool, detail: Dict[str, Any]) -> None:
        """healthz/readyz share one shape: the status CODE is the
        contract (200 ok / 503 not), the JSON body is detail."""
        body = json.dumps({"ok": bool(ok), **detail}, default=str) + "\n"
        self._send(handler, 200 if ok else 503, "application/json", body)

    def _route(self, handler) -> None:
        parsed = urlparse(handler.path)
        path = parsed.path.rstrip("/") or "/"
        if path == "/metrics":
            body = self.metrics_fn() if self.metrics_fn is not None else ""
            self.last_scrape_time = time.time()  # dslint: ignore[determinism] ds_report compares this against wall time; human-facing recency, not a span clock
            self.scrape_count += 1
            self._send(handler, 200, PROMETHEUS_CONTENT_TYPE, body)
        elif path == "/healthz":
            # no engine attached yet = the process itself is alive (a
            # router may health-check during the checkpoint load)
            ok, detail = (True, {"detail": "no engine attached"}) \
                if self.health_fn is None else self.health_fn()
            self._send_probe(handler, ok, detail)
        elif path == "/readyz":
            ok, detail = (False, {"reasons": ["initializing"]}) \
                if self.ready_fn is None else self.ready_fn()
            self._send_probe(handler, ok, detail)
        elif path == "/statusz":
            body = self.status_fn() if self.status_fn is not None \
                else "no engine attached\n"
            self._send(handler, 200, "text/plain; charset=utf-8", body)
        elif path == "/profilez":
            self._profilez(handler, parsed)
        elif path == "/":
            self._send(handler, 200, "text/plain; charset=utf-8",
                       "ds admin endpoints: /metrics /healthz /readyz "
                       "/statusz /profilez?seconds=N\n")
        else:
            self._send(handler, 404, "text/plain", f"no route {path}\n")

    def _profilez(self, handler, parsed) -> None:
        if not self.profile_dir:
            self._send(handler, 501, "text/plain",
                       "profiling disabled: no trace dir (start with "
                       "--trace-dir / ServingConfig.trace_dir)\n")
            return
        try:
            seconds = float(parse_qs(parsed.query).get("seconds", ["2"])[0])
        except ValueError:
            self._send(handler, 400, "text/plain",
                       "bad ?seconds= value (want a number)\n")
            return
        if not (0 < seconds <= self.max_profile_seconds):
            self._send(handler, 400, "text/plain",
                       f"seconds must be in (0, "
                       f"{self.max_profile_seconds:g}]\n")
            return
        # one capture at a time: a second concurrent request is told so
        # instead of silently corrupting the first capture
        if not self._profile_latch.acquire(blocking=False):
            self._send(handler, 409, "text/plain",
                       "a profile capture is already running\n")
            return
        try:
            path = self.profile_fn(seconds, self.profile_dir)
        except Exception as e:
            logger.error(f"admin /profilez capture failed: "
                         f"{type(e).__name__}: {e}")
            self._send(handler, 500, "text/plain",
                       f"profile capture failed: {type(e).__name__}: {e}\n")
            return
        finally:
            self._profile_latch.release()
        self._send(handler, 200, "application/json",
                   json.dumps({"profile": path, "seconds": seconds}) + "\n")


# ---------------------------------------------------------------------------
# serving-engine attachment
# ---------------------------------------------------------------------------

def serving_metrics_text(srv) -> str:
    """The /metrics body for a :class:`ServingEngine`: the unified
    registry (latency/SLO histograms, recompile + SLO counters, comm
    histograms when shared) plus the serving snapshot scalars and the
    per-program compile counts as labeled counters."""
    scalars: Dict[str, float] = dict(srv.metrics.snapshot())
    # whole-snapshot first: this renders on the scrape thread while the
    # engine owns compile_counts (the guarded-by=snapshot law)
    for prog, n in snapshot_items(srv.compile_counts):
        scalars[f"compile_count{{program={prog}}}"] = float(n)
    return render_prometheus(registry=srv.metrics.registry, scalars=scalars)


#: the compile ledger's columns of a program row (monitor/perf.py
#: CompileLedger.program): seconds traced, lowered and in the backend, and
#: whether the persistent cache served every compile — a cold start reads
#: "miss" and a backend_s of minutes, a warm one "hit" and a cache read
LEDGER_HEADER = f"{'trace_s':>9}{'lower_s':>9}{'backend_s':>11}{'cache':>7}"


def ledger_columns(row) -> str:
    cells = ["-" if row.get(k) is None else f"{row[k]:.2f}"
             for k in ("trace_s", "lower_s", "backend_s")]
    cells.append({True: "hit", False: "miss", None: "-"}[row.get("cache_hit")])
    return "".join(f"{c:>{w}}" for c, w in zip(cells, (9, 9, 11, 7)))


def memory_line(row) -> Optional[str]:
    """One line under a program's row that was built ahead of its first
    call (the train step, ``runtime/engine.py _fit_train_step``): what its
    remat policy keeps beyond the policy's own, of which budget, how often
    the compiled step made the engine take that back, and what
    ``memory_analysis()`` says the program occupies. None for a row
    without the numbers."""
    m = row.get("memory")
    if not m:
        return None
    gb = lambda key: f"{m[key] / 1e9:.2f}"
    return (f"  {row['name']}: keeps {m['remat_kept_names']} offered values, "
            f"{gb('remat_kept_bytes')} of {gb('remat_room_bytes')} GB room "
            f"({m['remat_fallbacks']} taken back); compiled: arguments "
            f"{gb('step_argument_bytes')} + temp {gb('step_temp_bytes')} GB, "
            f"peak {gb('step_peak_bytes')} GB")


def step_cost_line(row) -> Optional[str]:
    """One line under the TRAIN step's row (``monitor/perf.py StepCost``):
    the matrix operations a step runs and how many of them are replays,
    the largest scopes, and what the count leaves out by name. None for a
    row without the record."""
    c = row.get("step_cost")
    if not c:
        return None
    top = sorted(c["scopes"].items(), key=lambda kv: -sum(kv[1].values()))
    scopes = ", ".join(f"{k} {sum(v.values()) / 1e12:.3f}" for k, v in top[:6])
    left = ", ".join(f"{k} x{n}" for k, n in c["uncounted"].items()) or "none"
    return (f"  {row['name']}: {c['matmul_flops'] / 1e12:.3f} T matrix "
            f"operations a step ({c['replayed_flops'] / 1e12:.3f} replayed; "
            f"cond spread {c['cond_spread_flops'] / 1e12:.3f}); by scope: "
            f"{scopes}; uncounted: {left}; walked in {c['walk_s']:.3f} s")


def serving_statusz(srv) -> str:
    """The human-readable /statusz page of a serving engine: resident
    compiled-program table, recompile counts, HBM watermarks, and the
    metrics snapshot — ``ds_report``'s perf table, served over HTTP."""
    lines: List[str] = ["== deepspeed_tpu serving status ==", ""]
    perf = srv.perf_summary()
    lines.append(f"device: {perf.get('device_kind')} "
                 f"x{perf.get('n_devices')}")
    live, peak = perf.get("hbm_bytes_in_use"), perf.get("hbm_peak_bytes")
    if live is not None:
        lines.append(f"hbm: {live / 1e9:.2f}G in use, "
                     f"{(peak or 0) / 1e9:.2f}G peak")
    else:
        lines.append("hbm: no allocator stats on this backend")
    lines.append("")
    lines.append(f"{'program':<28}{'fingerprint':<13}{'compiles':>9}"
                 f"{'recompiles':>11}{'calls':>7}{LEDGER_HEADER}")
    for row in perf.get("programs", []):
        lines.append(f"{row['name']:<28}{str(row['fingerprint']):<13}"
                     f"{row['compiles']:>9}{row['recompiles']:>11}"
                     f"{row['calls']:>7}{ledger_columns(row)}")
        for line in filter(None, (memory_line(row), step_cost_line(row))):
            lines.append(line)
    lines.append("")
    lines.append(f"compile_counts: {json.dumps(perf.get('compile_counts'))}")
    lines.append("")
    tiers = srv.tier_status()
    if tiers.get("enabled"):
        lines.append(f"kv_tiers: {json.dumps(tiers['tiers'])}")
        lines.append("")
    quant = srv.quant_status()
    if quant.get("enabled"):
        lines.append(f"quantization: {json.dumps(quant)}")
        lines.append("")
    lines.append("metrics snapshot:")
    for k, v in sorted(srv.metrics.snapshot().items()):
        lines.append(f"  {k} = {v:g}")
    return "\n".join(lines) + "\n"


def attach_serving_engine(admin: AdminServer, srv) -> AdminServer:
    """Point an :class:`AdminServer`'s endpoints at a live
    :class:`ServingEngine`. Callbacks hold only a weak reference — the
    admin server (whose daemon thread outlives everything) must never
    keep a dropped engine alive; endpoints on a dead engine degrade to
    unhealthy/not-ready rather than erroring."""
    ref = weakref.ref(srv)

    def alive():
        eng = ref()
        if eng is None:
            return None
        return eng

    def metrics_fn() -> str:
        eng = alive()
        return "" if eng is None else serving_metrics_text(eng)

    def health_fn():
        eng = alive()
        if eng is None:
            return False, {"detail": "engine dropped"}
        return eng.health()

    def ready_fn():
        eng = alive()
        if eng is None:
            return False, {"reasons": ["engine dropped"]}
        return eng.readiness()

    def status_fn() -> str:
        eng = alive()
        return "engine dropped\n" if eng is None else serving_statusz(eng)

    admin.metrics_fn = metrics_fn
    admin.health_fn = health_fn
    admin.ready_fn = ready_fn
    admin.status_fn = status_fn
    if admin.profile_dir is None:
        admin.profile_dir = srv.config.trace_dir
    return admin


def serve_admin(srv, port: int, host: str = "127.0.0.1") -> AdminServer:
    """Build an :class:`AdminServer` already attached to a serving
    engine (the one-call path for tests and embedders; ``ds_serve`` binds
    the server before the model loads and attaches later)."""
    admin = AdminServer(port=port, host=host,
                        profile_dir=srv.config.trace_dir)
    return attach_serving_engine(admin, srv)


# ---------------------------------------------------------------------------
# fleet (ServingRouter) attachment
# ---------------------------------------------------------------------------

def fleet_metrics_text(router) -> str:
    """The /metrics body for a :class:`ServingRouter`: fleet-level
    counters under ``ds_fleet_*`` plus EVERY replica's serving snapshot
    and compile counts as ``replica=``-labeled series — one scrape shows
    the whole fleet, and a per-replica dashboard is one label filter."""
    scalars: Dict[str, float] = {
        f"fleet_{k}": v for k, v in router.metrics.snapshot().items()}
    autoscaler = getattr(router, "autoscaler", None)
    if autoscaler is not None:
        # the decision layer's own series (the scale TRANSITIONS are in
        # ds_fleet_scale_*; these are what the policy saw and chose)
        scalars.update({f"autoscale_{k}": v for k, v
                        in autoscaler.metrics.snapshot().items()})
    for rep in router.replicas:
        lbl = f"{{replica={rep.name}}}"
        scalars[f"replica_alive{lbl}"] = float(rep.alive)
        scalars[f"replica_ejected{lbl}"] = float(rep.ejected)
        scalars[f"replica_draining{lbl}"] = float(rep.draining)
        scalars[f"replica_retired{lbl}"] = float(rep.retired)
        scalars[f"replica_prefix_index_blocks{lbl}"] = float(
            rep.prefix_index_blocks())
        for k, v in rep.engine.metrics.snapshot().items():
            scalars[f"{k}{lbl}"] = v
        for prog, n in snapshot_items(rep.engine.compile_counts):
            scalars[f"compile_count{{program={prog},"
                    f"replica={rep.name}}}"] = float(n)
    return render_prometheus(scalars=scalars)


def fleet_statusz(router) -> str:
    """The human-readable fleet /statusz section: one row per replica
    (health, readiness, load, goodput, burn rate, prefix-index size,
    SLO verdicts) plus the router's routed/requeued/ejected counters."""
    st = router.status()
    lines: List[str] = ["== deepspeed_tpu serving fleet ==", ""]
    lines.append(f"routing: {st['routing']}"
                 + (f" (disaggregated; prefill replicas "
                    f"{st['prefill_replicas']})" if st["disaggregated"]
                    else ""))
    lines.append(f"fleet queue: {st['queue_depth']} queued, "
                 f"{st['in_flight']} in flight"
                 + (" [draining]" if st["draining"] else ""))
    lines.append(f"fleet goodput: {st['fleet_goodput_tokens_per_sec']:g} "
                 f"tok/s")
    lines.append("")
    lines.append(f"{'replica':<8}{'state':<22}{'queue':>6}{'active':>7}"
                 f"{'burn':>7}{'goodput':>9}{'pfx_blocks':>11}"
                 f"{'verdicts (g/tm/pm/s/f)':>24}")
    for row in st["replicas"]:
        state = "dead" if not row["alive"] else \
            ("ejected:" + ",".join(row["health_reasons"])
             if row["ejected"] else
             (",".join(row["ready_reasons"]) or "ready"))
        v = row["slo_verdicts"]
        verd = (f"{v['good']}/{v['ttft_miss']}/{v['tpot_miss']}"
                f"/{v['shed']}/{v['failed']}")
        lines.append(f"{row['replica']:<8}{state:<22}"
                     f"{row['queue_depth']:>6}{row['active_seqs']:>7}"
                     f"{row['slo_burn_rate']:>7.2f}"
                     f"{row['goodput_tokens_per_sec']:>9.1f}"
                     f"{row['prefix_index_blocks']:>11}{verd:>24}")
    lines.append("")
    j = st.get("journal")
    if j is not None:
        age = j["last_compaction_age_s"]
        lines.append(f"journal: {j['dir']} — {j['segments']} segment(s) "
                     f"/ {j['bytes']} bytes, {j['non_terminal']} "
                     f"non-terminal of {j['requests_tracked']} tracked, "
                     f"last compaction "
                     f"{'never' if age is None else f'{age:.0f}s ago'}")
    c = st["counters"]
    lines.append(f"routed: {int(c['routed_affinity'])} by prefix affinity, "
                 f"{int(c['routed_load'])} by load; "
                 f"requeued {int(c['requests_requeued'])}, "
                 f"rejected {int(c['requests_rejected'])}"
                 + (f", recovered {int(c['requests_recovered'])}"
                    if c.get("requests_recovered") else ""))
    lines.append(f"incidents: {int(c['replica_kills'])} kills, "
                 f"{int(c['replica_revives'])} revives, "
                 f"{int(c['ejections'])} ejections, "
                 f"{int(c['readmissions'])} readmissions")
    if c.get("scale_outs") or c.get("scale_ins") or c.get("scale_aborts") \
            or st.get("replicas_retired"):
        lines.append(f"elastic: {st['replicas_active']} active of "
                     f"{st['replicas_total']} slots "
                     f"({st['replicas_retired']} retired); "
                     f"{int(c['scale_outs'])} scale-outs, "
                     f"{int(c['scale_ins'])} scale-ins, "
                     f"{int(c['scale_aborts'])} aborts, "
                     f"{int(c['scale_warm_pages'])}+"
                     f"{int(c['scale_warm_pages_host'])} pages warmed "
                     f"(device+host)")
    autoscaler = getattr(router, "autoscaler", None)
    if autoscaler is not None:
        a = autoscaler.status()
        lines.append(f"autoscaler: {a['policy']}, bounds "
                     f"{a['bounds'][0]}..{a['bounds'][1]}, "
                     f"cooldown {a['cooldown_remaining']}/"
                     f"{a['cooldown_steps']} left, "
                     f"{int(a['counters']['scale_out_decisions'])} out / "
                     f"{int(a['counters']['scale_in_decisions'])} in "
                     f"decisions")
    if st["disaggregated"]:
        lines.append(f"disaggregation: {int(c['disagg_hops'])} hops, "
                     f"{int(c['kv_pages_transferred'])} KV pages "
                     f"transferred")
    return "\n".join(lines) + "\n"


def attach_fleet(admin: AdminServer, router) -> AdminServer:
    """Point an :class:`AdminServer` at a live :class:`ServingRouter`:
    /healthz is fleet liveness (200 while ANY replica can serve),
    /readyz is fleet readiness (200 while any replica is routable and
    ready), /metrics carries every replica with ``replica=`` labels.
    Weak reference, same as the engine attachment."""
    ref = weakref.ref(router)

    def alive():
        return ref()

    def metrics_fn() -> str:
        r = alive()
        return "" if r is None else fleet_metrics_text(r)

    def health_fn():
        r = alive()
        if r is None:
            return False, {"detail": "router dropped"}
        healthy = [rep.name for rep in r.replicas
                   if rep.probe_health(r.cfg.heartbeat_stale_s)[0]]
        return bool(healthy), {"healthy_replicas": healthy,
                               "replicas": len(r.replicas)}

    def ready_fn():
        r = alive()
        if r is None:
            return False, {"reasons": ["router dropped"]}
        routable = [rep.name for rep in r.replicas
                    if rep.routable and not rep.ready_reasons()]
        reasons = [] if routable else ["no ready replica"]
        if r._draining:
            reasons.append("draining")
        return (not reasons), {"reasons": reasons,
                               "ready_replicas": routable}

    def status_fn() -> str:
        r = alive()
        return "router dropped\n" if r is None else fleet_statusz(r)

    admin.metrics_fn = metrics_fn
    admin.health_fn = health_fn
    admin.ready_fn = ready_fn
    admin.status_fn = status_fn
    return admin
