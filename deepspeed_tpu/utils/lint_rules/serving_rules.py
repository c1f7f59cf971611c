"""Serving-scoped rules: host-sync, terminal-path, determinism.

These rules key on WHERE code lives (the serving package, the monitor
package, jitted bodies) rather than on annotations — the invariants they
enforce are properties of those subsystems as a whole:

- **host-sync** — the unified serving step syncs the device exactly once
  per step, at harvest. Any other ``np.asarray`` / ``jax.device_get`` /
  ``.block_until_ready()`` inside ``ServingEngine`` stalls the packed
  dispatch pipeline; the declared harvest sites live in
  ``HOST_SYNC_ALLOW`` below (change it deliberately, in review).
- **terminal-write** — every terminal transition funnels through
  ``Scheduler._release`` (pages back to the pool, SLO hook, terminal
  span), and every FLEET-level terminal through
  ``ServingRouter._fleet_release`` (the router-side mirror: terminal
  counters, finish bookkeeping). A bare ``req.state =
  RequestState.FAILED`` anywhere else leaks pages structurally — and a
  fleet requeue path that calls ``_release`` DIRECTLY (instead of the
  cancel/fail/timeout API) skips the SLO hook and the terminal span, so
  direct ``_release`` calls outside ``scheduler.py`` are findings too.
- **acquire-release** — a page acquire inside a ``try`` whose handlers
  swallow without releasing strands pages on the exception edge.
- **determinism** — ``time.perf_counter`` is the one serving clock
  (spans, deadlines, SLO verdicts all stamp it); randomness rides the
  seeded jax PRNG streams. ``time.time`` / ``random`` / ``np.random``
  in serving, monitor, or jitted code breaks replayability.
"""

import ast
from typing import List, Set

from .core import FileCtx, Finding
from .trace_safety import find_jit_scopes

#: ServingEngine methods where a device sync is the DESIGN (the one
#: harvest sync per step, and caller-input coercion at submit)
HOST_SYNC_ALLOW = {"submit", "_step", "_step_mixed", "_prefill",
                   "_prefill_chunk"}

_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "jax.device_get"}

_TERMINAL_STATES = {"FINISHED", "FAILED", "TIMEOUT", "CANCELLED"}
_NONTERMINAL_STATES = {"QUEUED", "RUNNING"}
#: the only places terminal bookkeeping may be written: the scheduler's
#: release (engine level) and the router's mirror (fleet level)
_TERMINAL_ALLOW_FUNCS = {"_release", "_fleet_release"}

_ACQUIRE_METHODS = {"allocate", "acquire", "cow"}

#: journal append verbs and the ONLY router methods allowed to call them
#: (``journal.py`` itself owns its internals and is exempt): the WAL
#: ordering — admit before the door accepts, watermark before the caller
#: observes tokens, verdict at the terminal funnel — lives in exactly
#: these seams, so an append anywhere else is a finding even when it
#: "works": it silently changes what a crash can lose
_JOURNAL_APPEND_METHODS = {"append_admit", "append_deliver",
                           "append_terminal"}
_JOURNAL_ALLOW_FUNCS = {"submit", "_deliver", "_fleet_release"}
#: the fleet-membership WAL has its own seam: scale records append only
#: from the router's begin/commit/abort trio (intent before any state
#: changes, done after the transition, abort when interrupted) — an
#: append_scale anywhere else changes what membership a crash recovers
_SCALE_APPEND_METHODS = {"append_scale"}
_SCALE_ALLOW_FUNCS = {"begin_scale", "commit_scale", "abort_scale"}


def _dotted(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _is_serving(ctx: FileCtx) -> bool:
    return "inference/serving/" in ctx.norm_path


def _is_monitor(ctx: FileCtx) -> bool:
    return "/monitor/" in ctx.norm_path or \
        ctx.norm_path.startswith("deepspeed_tpu/monitor/")


def check(ctx: FileCtx) -> List[Finding]:
    out: List[Finding] = []
    out.extend(_check_host_sync(ctx))
    if _is_serving(ctx):
        out.extend(_check_terminal(ctx))
        out.extend(_check_release_calls(ctx))
        out.extend(_check_acquire_release(ctx))
        out.extend(_check_journal_writes(ctx))
    out.extend(_check_determinism(ctx))
    return out


# -- host-sync ---------------------------------------------------------

def _check_host_sync(ctx: FileCtx) -> List[Finding]:
    if not ctx.norm_path.endswith("inference/serving/engine.py"):
        return []
    out: List[Finding] = []
    for cls in ast.walk(ctx.tree):
        if not (isinstance(cls, ast.ClassDef)
                and cls.name == "ServingEngine"):
            continue
        for method in cls.body:
            if not isinstance(method, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                continue
            if method.name in HOST_SYNC_ALLOW:
                continue
            for node in ast.walk(method):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                name = _dotted(f)
                if name in _SYNC_CALLS:
                    out.append(ctx.finding(
                        node, "host-sync",
                        f"{name}() in serving hot path "
                        f"ServingEngine.{method.name} (not an "
                        f"allowlisted harvest site)"))
                elif isinstance(f, ast.Attribute) and \
                        f.attr == "block_until_ready":
                    out.append(ctx.finding(
                        node, "host-sync",
                        f".block_until_ready() in serving hot path "
                        f"ServingEngine.{method.name}"))
    return out


# -- terminal-path -----------------------------------------------------

def _enclosing_func_name(ctx: FileCtx, node: ast.AST) -> str:
    fn = ctx.enclosing(node, (ast.FunctionDef, ast.AsyncFunctionDef))
    return fn.name if fn is not None else ""


def _check_terminal(ctx: FileCtx) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.Assign, ast.AugAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if not isinstance(t, ast.Attribute):
                continue
            fname = _enclosing_func_name(ctx, node)
            if fname in _TERMINAL_ALLOW_FUNCS:
                continue
            if t.attr == "state":
                value = getattr(node, "value", None)
                if _is_nonterminal_state(value):
                    continue
                if _mentions_request_state(value) or \
                        _is_terminal_state(value):
                    out.append(ctx.finding(
                        node, "terminal-write",
                        f"Request.state written outside "
                        f"Scheduler._release (in {fname or 'module'}) "
                        f"— terminal transitions must funnel through "
                        f"_release"))
            elif t.attr in ("finish_reason", "finish_time"):
                out.append(ctx.finding(
                    node, "terminal-write",
                    f"terminal bookkeeping .{t.attr} written outside "
                    f"Scheduler._release"))
    return out


def _is_terminal_state(value) -> bool:
    return isinstance(value, ast.Attribute) and \
        value.attr in _TERMINAL_STATES and \
        isinstance(value.value, ast.Name) and \
        value.value.id == "RequestState"


def _is_nonterminal_state(value) -> bool:
    return isinstance(value, ast.Attribute) and \
        value.attr in _NONTERMINAL_STATES and \
        isinstance(value.value, ast.Name) and \
        value.value.id == "RequestState"


def _mentions_request_state(value) -> bool:
    if value is None:
        return False
    return any(isinstance(n, ast.Name) and n.id in ("RequestState", "state")
               for n in ast.walk(value))


def _check_release_calls(ctx: FileCtx) -> List[Finding]:
    """Fleet requeue / redispatch paths (the router's cancel, eject and
    kill handling) must reach terminal state through the scheduler's
    cancel/fail/timeout API — a direct ``_release`` call from outside
    ``scheduler.py`` would still return the pages but bypass nothing
    visibly, which is exactly why it is banned: the API wrappers ARE
    the one audited seam (and ``_fleet_release`` is the router's own
    terminal funnel, not a scheduler entry point)."""
    if ctx.norm_path.endswith("inference/serving/scheduler.py"):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "_release"):
            continue
        out.append(ctx.finding(
            node, "terminal-write",
            f"direct Scheduler._release call in "
            f"{_enclosing_func_name(ctx, node) or 'module'} — fleet "
            f"requeue/cancel paths must use the scheduler's "
            f"cancel/fail/timeout API (or ServingRouter._fleet_release "
            f"for fleet-level terminals)"))
    return out


def _check_journal_writes(ctx: FileCtx) -> List[Finding]:
    """The journal's write-ahead seam: appends only from the router
    methods that carry the ordering contract. ``journal.py`` itself is
    exempt (recovery/compaction are its internals)."""
    if ctx.norm_path.endswith("inference/serving/journal.py"):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in (_JOURNAL_APPEND_METHODS
                                       | _SCALE_APPEND_METHODS)):
            continue
        fname = _enclosing_func_name(ctx, node)
        scale = node.func.attr in _SCALE_APPEND_METHODS
        allow = _SCALE_ALLOW_FUNCS if scale else _JOURNAL_ALLOW_FUNCS
        if fname in allow:
            continue
        out.append(ctx.finding(
            node, "journal-write",
            f"journal {node.func.attr}() in {fname or 'module'} — "
            f"appends must ride the router's write-ahead seam "
            f"({'/'.join(sorted(allow))}) so the "
            f"crash-recovery ordering contract holds"))
    return out


def _check_acquire_release(ctx: FileCtx) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Try):
            continue
        acquires = []
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr in _ACQUIRE_METHODS:
                    acquires.append(sub)
        if not acquires:
            continue
        edges = list(node.handlers) + list(node.finalbody)
        released = False
        for edge in edges:
            for sub in ast.walk(edge):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr == "free":
                    released = True
                if isinstance(sub, ast.Raise):
                    released = True  # re-raised: caller's _release runs
        if edges and not released:
            out.append(ctx.finding(
                acquires[0], "acquire-release",
                "page acquire inside a try whose except/finally never "
                "releases — pages strand on the exception edge"))
    return out


# -- determinism -------------------------------------------------------

def _import_aliases(ctx: FileCtx) -> dict:
    """Local binding -> fully-dotted import path, covering every import
    style (``import random as rnd``, ``from time import time``, ``from
    numpy import random``). Resolution goes THROUGH this map only, so a
    local variable that merely shares a module's name never flags."""
    out: dict = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    out[a.asname] = a.name
                else:
                    # `import numpy.random` binds the TOP name
                    top = a.name.split(".")[0]
                    out[top] = top
        elif isinstance(node, ast.ImportFrom) and node.module and \
                node.level == 0:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def _resolved_call_name(node: ast.Call, aliases: dict) -> str:
    """The called function's import-resolved dotted path, '' when the
    call root is not an imported binding."""
    parts: List[str] = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if not isinstance(f, ast.Name):
        return ""
    root = aliases.get(f.id)
    if root is None:
        return ""
    return ".".join([root] + list(reversed(parts)))


def _jit_lines(ctx: FileCtx) -> Set[int]:
    lines: Set[int] = set()
    for fn in find_jit_scopes(ctx):
        end = getattr(fn, "end_lineno", fn.lineno)
        lines.update(range(fn.lineno, end + 1))
    return lines


def _check_determinism(ctx: FileCtx) -> List[Finding]:
    in_scope_file = _is_serving(ctx) or _is_monitor(ctx)
    jit_lines: Set[int] = set() if in_scope_file else _jit_lines(ctx)
    if not in_scope_file and not jit_lines:
        return []
    aliases = _import_aliases(ctx)
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if not in_scope_file and node.lineno not in jit_lines:
            continue
        name = _resolved_call_name(node, aliases)
        if not name:
            continue
        where = "serving/monitor code" if in_scope_file \
            else "a jitted function"
        if name == "time.time":
            out.append(ctx.finding(
                node, "determinism",
                f"time.time() in {where} — time.perf_counter is the "
                f"clock every span/deadline stamps"))
        elif name.startswith("numpy.random."):
            out.append(ctx.finding(
                node, "determinism",
                f"{name}() in {where} — randomness must ride the "
                f"seeded jax PRNG streams"))
        elif name == "random" or name.startswith("random."):
            # stdlib random resolved through an import (the alias map
            # never maps a local variable), incl. `from random import
            # random` which resolves to exactly "random.random"
            out.append(ctx.finding(
                node, "determinism",
                f"stdlib {name}() in {where} — randomness must ride "
                f"the seeded jax PRNG streams"))
    return out
