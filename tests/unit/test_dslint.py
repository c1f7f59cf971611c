"""dslint (``tools/dslint.py`` + ``deepspeed_tpu/utils/lint_rules/``).

Three layers, mirroring how the gate is used:

1. **Golden fixtures** — for every rule, one minimal true-positive
   snippet (finding asserted by rule id + line) and one near-miss
   true-negative (the pattern that LOOKS like a violation but is the
   blessed idiom). These are the rule-semantics contract.
2. **Pragma + baseline semantics** — ignore-with-reason suppresses,
   ignore-without-reason is itself a finding, the baseline forgives
   exactly one occurrence per entry and never resurrects on line drift.
3. **The gate itself** — the shipped tree is clean (CLI exits 0, in
   well under the 10s bar), and seeding one violation of each rule
   family into a scratch copy of the real ``engine.py`` flips the gate
   non-zero naming the rule and ``path:line``.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from deepspeed_tpu.utils.lint_rules import (RULES, lint_status,
                                            load_baseline, run_lint,
                                            write_baseline)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
PKG = os.path.join(REPO, "deepspeed_tpu")
DSLINT = os.path.join(REPO, "tools", "dslint.py")
BASELINE = os.path.join(REPO, "tools", "dslint_baseline.json")


def lint_src(tmp_path, source, name="mod.py", subdir=""):
    """Write ``source`` under tmp and lint it; returns the report."""
    d = tmp_path / subdir if subdir else tmp_path
    d.mkdir(parents=True, exist_ok=True)
    f = d / name
    f.write_text(textwrap.dedent(source))
    return run_lint([str(tmp_path)])


def rules_at(report, rule):
    return [f for f in report.findings if f.rule == rule]


def line_of(source, marker):
    for i, ln in enumerate(textwrap.dedent(source).splitlines(), 1):
        if marker in ln:
            return i
    raise AssertionError(f"marker {marker!r} not in fixture")


# ---------------------------------------------------------------------------
# golden fixtures: trace-safety
# ---------------------------------------------------------------------------

def test_trace_branch_positive(tmp_path):
    src = """
    import jax

    def prog(x):
        if x > 0:
            x = x + 1
        return x

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "trace-branch")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, "if x > 0:")


def test_trace_branch_near_misses(tmp_path):
    # closure flag (static), `is None` static-arg check, and the same
    # branch in a function that is never jitted: all quiet
    src = """
    import jax

    flag = True

    def prog(x, k):
        if flag:
            x = x + 1
        if k is None:
            return x
        return x + k

    prog_j = jax.jit(prog)

    def host_only(x):
        if x > 0:
            return 1
        return 0
    """
    report = lint_src(tmp_path, src)
    assert not rules_at(report, "trace-branch")


def test_trace_host_cast_positive(tmp_path):
    src = """
    import jax

    def prog(x):
        n = int(x)
        m = x.sum().item()
        return n + m

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "trace-host-cast")
    assert {h.line for h in hits} == {line_of(src, "int(x)"),
                                      line_of(src, ".item()")}


def test_trace_host_cast_near_miss(tmp_path):
    # casting a closure static is fine; .item() outside jit is fine
    src = """
    import jax

    width = "8"

    def prog(x):
        n = int(width)
        return x * n

    prog_j = jax.jit(prog)

    def host(arr):
        return arr.item()
    """
    report = lint_src(tmp_path, src)
    assert not rules_at(report, "trace-host-cast")


def test_trace_closure_state_positive_and_pragma(tmp_path):
    src = """
    import jax

    counts = {"n": 0}
    blessed = {"n": 0}

    def prog(x):
        counts["n"] += 1
        blessed["n"] += 1  # dslint: ignore[trace-closure-state] compile counter by design
        return x

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "trace-closure-state")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, 'counts["n"] += 1')
    assert len(report.suppressed) == 1


def test_trace_closure_state_near_miss(tmp_path):
    # mutating a LOCAL container inside the jitted body is fine
    src = """
    import jax

    def prog(x):
        acc = {}
        acc["n"] = 1
        return x

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src)
    assert not rules_at(report, "trace-closure-state")


def test_trace_shape_arith_positive(tmp_path):
    src = """
    import jax

    def prog(x):
        acc = 0
        for i in range(x.shape[0]):
            acc = acc + i
        return acc

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "trace-shape-arith")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, "for i in range(x.shape[0]):")


def test_trace_shape_arith_near_miss(tmp_path):
    src = """
    import jax

    LAYERS = 4

    def prog(x):
        acc = 0
        for i in range(LAYERS):
            acc = acc + i
        return acc

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src)
    assert not rules_at(report, "trace-shape-arith")


# ---------------------------------------------------------------------------
# golden fixtures: host-sync
# ---------------------------------------------------------------------------

_HOST_SYNC_SRC = """
import jax
import numpy as np


class ServingEngine:
    def _grow_pages(self, x):
        return np.asarray(x)

    def _step(self, x):
        return np.asarray(x)
"""


def test_host_sync_positive_and_allowlist(tmp_path):
    report = lint_src(tmp_path, _HOST_SYNC_SRC, name="engine.py",
                      subdir="inference/serving")
    hits = rules_at(report, "host-sync")
    assert len(hits) == 1
    assert hits[0].line == line_of(_HOST_SYNC_SRC,
                                   "return np.asarray(x)")  # _grow_pages
    assert "_grow_pages" in hits[0].message


def test_host_sync_scoped_to_serving_engine_file(tmp_path):
    # the same class/calls anywhere else are not the serving hot path
    report = lint_src(tmp_path, _HOST_SYNC_SRC, name="engine.py",
                      subdir="somewhere/else")
    assert not rules_at(report, "host-sync")


# ---------------------------------------------------------------------------
# golden fixtures: lock-discipline
# ---------------------------------------------------------------------------

def test_lock_guarded_positive_negative_snapshot(tmp_path):
    src = """
    import threading


    class Ring:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0  # dslint: guarded-by=_lock

        def inc(self):
            with self._lock:
                self._count += 1

        def peek(self):
            return self._count

        def snap(self):  # dslint: snapshot
            return self._count
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "lock-guarded")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, "return self._count")  # peek


def test_lock_guarded_module_global(tmp_path):
    src = """
    import threading

    _LOCK = threading.Lock()
    _REG = {}  # dslint: guarded-by=_LOCK


    def good():
        with _LOCK:
            _REG["a"] = 1


    def bad():
        _REG["b"] = 2
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "lock-guarded")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, '_REG["b"] = 2')


def test_lock_snapshot_iteration_and_double_read(tmp_path):
    src = """
    class Eng:
        def __init__(self):
            self.programs = {}  # dslint: guarded-by=snapshot
            self._wedged = None  # dslint: guarded-by=snapshot

        def ok_get(self, k):
            return self.programs.get(k)

        def ok_list(self):
            return list(self.programs.items())

        def bad_sorted(self):
            return sorted(self.programs.items())

        def bad_for(self):
            return [k for k in self.programs]

        def bad_double(self):
            return self._wedged is not None and self._wedged.is_alive()

        def ok_single(self):
            w = self._wedged
            return w is not None and w.is_alive()
    """
    report = lint_src(tmp_path, src)
    hits = rules_at(report, "lock-snapshot")
    lines = {h.line for h in hits}
    assert line_of(src, "sorted(self.programs.items())") in lines
    assert line_of(src, "for k in self.programs") in lines
    assert line_of(src, "self._wedged is not None and") in lines
    assert len(hits) == 3  # the ok_* accessors stay quiet


def test_lock_snapshot_cross_module_by_field_name(tmp_path):
    # the declaration lives in one module, the violating read in another
    # (the scrape-path shape: monitor code iterating engine fields)
    (tmp_path / "eng.py").write_text(textwrap.dedent("""
    class Eng:
        def __init__(self):
            self.compile_counts = {}  # dslint: guarded-by=snapshot
    """))
    scrape = """
    def render(srv):
        return [k for k, v in srv.compile_counts.items()]
    """
    (tmp_path / "scrape.py").write_text(textwrap.dedent(scrape))
    report = run_lint([str(tmp_path)])
    hits = rules_at(report, "lock-snapshot")
    assert len(hits) == 1
    assert hits[0].path.endswith("scrape.py")


# ---------------------------------------------------------------------------
# golden fixtures: terminal-path
# ---------------------------------------------------------------------------

def test_terminal_write_positive_negative(tmp_path):
    src = """
    class RequestState:
        FAILED = "failed"
        RUNNING = "running"


    class Scheduler:
        def _release(self, req, state):
            req.state = state
            req.finish_reason = "done"

        def fail_bare(self, req):
            req.state = RequestState.FAILED

        def admit(self, req):
            req.state = RequestState.RUNNING

        def stamp(self, req):
            req.finish_time = 1.0
    """
    report = lint_src(tmp_path, src, name="sched.py",
                      subdir="inference/serving")
    hits = rules_at(report, "terminal-write")
    lines = {h.line for h in hits}
    assert line_of(src, "req.state = RequestState.FAILED") in lines
    assert line_of(src, "req.finish_time = 1.0") in lines
    assert len(hits) == 2  # _release and the RUNNING write stay quiet


def test_release_call_outside_scheduler_flagged(tmp_path):
    """Fleet requeue paths (router-side cancel/redispatch) must go
    through the scheduler's cancel/fail/timeout API — a direct
    ``_release`` call from router code is a finding."""
    src = """
    def requeue_stranded(self, req):
        self.sched._release(req, "cancelled", "replica_kill")
    """
    report = lint_src(tmp_path, src, name="router.py",
                      subdir="inference/serving")
    hits = rules_at(report, "terminal-write")
    assert len(hits) == 1
    assert "cancel/fail/timeout" in hits[0].message
    assert hits[0].line == line_of(src, "._release(")


def test_release_call_allowed_in_scheduler_and_fleet_release(tmp_path):
    """scheduler.py's own wrappers call ``_release`` freely, and the
    router's ``_fleet_release`` is the allowed fleet-level terminal
    funnel (terminal writes there stay quiet)."""
    sched = """
    class Scheduler:
        def cancel(self, req, reason):
            self._release(req, "cancelled", reason)
    """
    lint_src(tmp_path, sched, name="scheduler.py",
             subdir="inference/serving")
    router = """
    class RequestState:
        FAILED = "failed"


    class ServingRouter:
        def _fleet_release(self, freq, state, reason):
            freq.state = RequestState.FAILED
            freq.finish_reason = reason
            freq.finish_time = 1.0
    """
    report = lint_src(tmp_path, router, name="router.py",
                      subdir="inference/serving")
    assert not rules_at(report, "terminal-write")


def test_journal_write_outside_wal_seam_flagged(tmp_path):
    """Journal appends carry the write-ahead ordering contract — an
    append from anywhere but the router's submit/_deliver/_fleet_release
    seam is a finding, even when it 'works'."""
    src = """
    class ServingRouter:
        def submit(self, prompt):
            self.journal.append_admit("f1", prompt, 8)

        def _deliver(self, freq, out):
            self.journal.append_deliver(freq.fid, out.tokens)

        def _fleet_release(self, freq, state, reason):
            self.journal.append_terminal(freq.fid, state, reason)

        def _collect(self):
            self.journal.append_terminal("f1", "finished", "length")
    """
    report = lint_src(tmp_path, src, name="router.py",
                      subdir="inference/serving")
    hits = rules_at(report, "journal-write")
    assert len(hits) == 1  # the three seam methods stay quiet
    assert hits[0].line == line_of(src, '"finished", "length"')
    assert "write-ahead seam" in hits[0].message


def test_journal_write_exempt_in_journal_module_and_elsewhere(tmp_path):
    """journal.py owns its internals (recovery / compaction), and
    non-serving files are out of scope entirely."""
    src = """
    class RequestJournal:
        def _replay_helper(self):
            self.append_terminal("f1", "finished", "length")
    """
    report = lint_src(tmp_path, src, name="journal.py",
                      subdir="inference/serving")
    assert not rules_at(report, "journal-write")
    report = lint_src(tmp_path, src, name="other.py")
    assert not rules_at(report, "journal-write")


def test_terminal_write_scoped_to_serving(tmp_path):
    src = """
    class RequestState:
        FAILED = "failed"


    def fail_bare(req):
        req.state = RequestState.FAILED
    """
    report = lint_src(tmp_path, src, name="other.py")
    assert not rules_at(report, "terminal-write")


def test_acquire_release_positive_negative(tmp_path):
    src = """
    def risky(pool, rid, work):
        blocks = []
        try:
            blocks = pool.allocate(2, rid)
            work(blocks)
        except Exception:
            pass
        return blocks


    def safe(pool, rid, work):
        blocks = []
        try:
            blocks = pool.allocate(2, rid)
            work(blocks)
        except Exception:
            pool.free(blocks, rid)
            raise
        return blocks
    """
    report = lint_src(tmp_path, src, name="alloc.py",
                      subdir="inference/serving")
    hits = rules_at(report, "acquire-release")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, "blocks = pool.allocate(2, rid)")


# ---------------------------------------------------------------------------
# golden fixtures: determinism
# ---------------------------------------------------------------------------

def test_determinism_positive(tmp_path):
    src = """
    import random
    import time

    import numpy as np


    def stamp():
        return time.time()


    def jitter():
        return random.random() + np.random.rand()
    """
    report = lint_src(tmp_path, src, name="clock.py",
                      subdir="inference/serving")
    hits = rules_at(report, "determinism")
    assert {h.line for h in hits} == {
        line_of(src, "time.time()"),
        line_of(src, "random.random() + np.random.rand()")}
    assert len(hits) == 3  # random.random and np.random.rand both flag


def test_determinism_near_miss(tmp_path):
    # perf_counter in serving is the law; time.time OUTSIDE the scoped
    # packages (and outside any jitted body) is nobody's business
    (tmp_path / "inference" / "serving").mkdir(parents=True)
    (tmp_path / "inference" / "serving" / "clock.py").write_text(
        "import time\n\ndef stamp():\n    return time.perf_counter()\n")
    (tmp_path / "host_tool.py").write_text(
        "import time\n\ndef stamp():\n    return time.time()\n")
    report = run_lint([str(tmp_path)])
    assert not rules_at(report, "determinism")


def test_determinism_in_jit_scope_anywhere(tmp_path):
    src = """
    import time

    import jax


    def prog(x):
        t = time.time()
        return x, t

    prog_j = jax.jit(prog)
    """
    report = lint_src(tmp_path, src, name="anywhere.py")
    hits = rules_at(report, "determinism")
    assert len(hits) == 1
    assert hits[0].line == line_of(src, "time.time()")


# ---------------------------------------------------------------------------
# pragma + baseline semantics
# ---------------------------------------------------------------------------

def test_ignore_pragma_without_reason_is_a_finding(tmp_path):
    src = """
    import time


    def stamp():
        return time.time()  # dslint: ignore[determinism]
    """
    report = lint_src(tmp_path, src, name="clock.py",
                      subdir="inference/serving")
    # the bare pragma does NOT suppress, and is itself a finding
    assert rules_at(report, "determinism")
    bad = rules_at(report, "bad-pragma")
    assert len(bad) == 1 and "reason" in bad[0].message


def test_ignore_pragma_unknown_rule_and_directive(tmp_path):
    src = """
    x = 1  # dslint: ignore[no-such-rule] because
    y = 2  # dslint: frobnicate
    """
    report = lint_src(tmp_path, src)
    msgs = [f.message for f in rules_at(report, "bad-pragma")]
    assert len(msgs) == 2
    assert any("unknown rule" in m for m in msgs)
    assert any("unknown dslint directive" in m for m in msgs)


def test_ignore_pragma_with_reason_suppresses(tmp_path):
    src = """
    import time


    def stamp():
        return time.time()  # dslint: ignore[determinism] wall clock of record for humans
    """
    report = lint_src(tmp_path, src, name="clock.py",
                      subdir="inference/serving")
    assert not report.findings
    assert len(report.suppressed) == 1
    assert report.pragma_count == 1


def test_baseline_forgives_exactly_one_occurrence_each(tmp_path):
    src = ("import time\n\n\ndef a():\n    return time.time()\n")
    d = tmp_path / "inference" / "serving"
    d.mkdir(parents=True)
    (d / "clock.py").write_text(src)
    first = run_lint([str(tmp_path)])
    assert len(first.findings) == 1

    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), first.findings)
    baseline = load_baseline(str(bl_path))

    # baselined: gate is clean — and stays clean when the line DRIFTS
    (d / "clock.py").write_text("X = 1\n\n\n" + src)
    drifted = run_lint([str(tmp_path)], baseline=baseline)
    assert not drifted.findings and len(drifted.baselined) == 1

    # a SECOND identical occurrence is new — one entry forgives one
    (d / "clock.py").write_text(
        src + "\n\ndef b():\n    return time.time()\n")
    second = run_lint([str(tmp_path)], baseline=baseline)
    assert len(second.findings) == 1 and len(second.baselined) == 1


def test_lint_status_shape(tmp_path):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "ok.py").write_text("x = 1\n")
    st = lint_status(str(d))
    assert st["verdict"] == "clean"
    assert st["rules"] == len(RULES)
    assert st["files"] == 1
    assert st["findings"] == 0


# ---------------------------------------------------------------------------
# the gate: shipped tree is clean, fast, and seedable
# ---------------------------------------------------------------------------

def test_shipped_tree_is_clean_in_process():
    t0 = time.perf_counter()
    report = run_lint([PKG], baseline=load_baseline(BASELINE))
    dt = time.perf_counter() - t0
    assert not report.findings, \
        "\n".join(f.render() for f in report.findings)
    assert dt < 10.0, f"dslint took {dt:.1f}s (bar: 10s)"
    # the shipped baseline holds NOTHING for serving/ and monitor/ —
    # those packages are clean by construction, not by grandfathering
    for e in load_baseline(BASELINE):
        assert "inference/serving/" not in e["path"]
        assert "deepspeed_tpu/monitor/" not in e["path"]


def test_cli_gate_exits_zero_on_shipped_tree():
    proc = subprocess.run(
        [sys.executable, DSLINT, "--check", "deepspeed_tpu/"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_list_rules():
    proc = subprocess.run([sys.executable, DSLINT, "--list-rules"],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 0
    for rid in RULES:
        assert rid in proc.stdout


_ENGINE = os.path.join(PKG, "inference", "serving", "engine.py")

#: one seed per rule family: (family, unique anchor in engine.py,
#: replacement, rule id the gate must name). Anchors are asserted
#: unique so engine edits that break a seed fail loudly here.
_SEEDS = [
    ("trace-safety", None,  # appended at EOF instead of replaced
     '\n\ndef _dslint_seed_prog(x):\n'
     '    if x > 0:\n'
     '        x = x + 1\n'
     '    return x\n\n\n'
     '_dslint_seed_fn = jax.jit(_dslint_seed_prog)\n',
     "trace-branch", "if x > 0:"),
    ("host-sync",
     "        keep = req.seq_len // self.block_pool.block_size + 1\n",
     "        keep = req.seq_len // self.block_pool.block_size + 1\n"
     "        _seed = jax.device_get(self._seq_lens)\n",
     "host-sync", "jax.device_get(self._seq_lens)"),
    ("lock-discipline",
     "    with _live_engines_lock:\n        return list(_LIVE_ENGINES)\n",
     "    return list(_LIVE_ENGINES)\n",
     "lock-guarded", "return list(_LIVE_ENGINES)"),
    ("terminal-path",
     '        self.sched.fail(req, "corrupt_logits")\n',
     "        req.state = RequestState.FAILED\n",
     "terminal-write", "req.state = RequestState.FAILED"),
    ("determinism",
     "        t0 = time.perf_counter()\n",
     "        t0 = time.time()\n",
     "determinism", "t0 = time.time()"),
]


@pytest.mark.parametrize("family,anchor,replacement,rule,marker",
                         _SEEDS, ids=[s[0] for s in _SEEDS])
def test_seeded_violation_flips_the_gate(tmp_path, family, anchor,
                                         replacement, rule, marker):
    """Acceptance drill: seed ONE violation of each rule family into a
    scratch copy of the real engine.py — the CLI gate must exit non-zero
    naming the rule and path:line."""
    scratch = tmp_path / "inference" / "serving"
    scratch.mkdir(parents=True)
    src = open(_ENGINE).read()
    if anchor is None:
        seeded = src + replacement
    else:
        assert src.count(anchor) == 1, \
            f"seed anchor for {family} no longer unique in engine.py"
        seeded = src.replace(anchor, replacement)
    path = scratch / "engine.py"
    path.write_text(seeded)

    # expected line: last occurrence covers the EOF-appended trace seed
    exp_line = max(i for i, ln in enumerate(seeded.splitlines(), 1)
                   if marker in ln)

    proc = subprocess.run(
        [sys.executable, DSLINT, "--check", str(tmp_path),
         "--baseline", "none"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert f"[{rule}]" in proc.stdout
    assert f"engine.py:{exp_line}:" in proc.stdout


def test_ds_report_dslint_section(capsys):
    """ds_report gains the dslint status section: verdict, rule count,
    baseline size, ignore-pragma count."""
    from deepspeed_tpu import env_report

    env_report.dslint_report()
    out = capsys.readouterr().out
    assert "dslint:" in out
    assert f"{len(RULES)} rules" in out
    assert "baseline" in out and "ignore pragma" in out
    assert "clean" in out  # the shipped tree verdict


def test_orphan_guard_pragma_is_a_finding(tmp_path):
    """A guarded-by pragma that binds to nothing (e.g. written on its
    own line above the assignment, where ignore pragmas ARE honored)
    must FAIL the gate — the alternative is a field everyone believes
    protected that is never checked."""
    src = """
    import threading


    class Ring:
        def __init__(self):
            self._lock = threading.Lock()
            # dslint: guarded-by=_lock
            self._count = 0

        def peek(self):
            return self._count
    """
    report = lint_src(tmp_path, src)
    bad = rules_at(report, "bad-pragma")
    assert len(bad) == 1
    assert "NOT being checked" in bad[0].message
    assert bad[0].line == line_of(src, "# dslint: guarded-by=_lock")


def test_orphan_snapshot_pragma_is_a_finding(tmp_path):
    src = """
    class Ring:
        def snap(self):
            # dslint: snapshot
            return 1
    """
    report = lint_src(tmp_path, src)
    bad = rules_at(report, "bad-pragma")
    assert len(bad) == 1 and "def" in bad[0].message


def test_determinism_sees_from_imports_and_aliases(tmp_path):
    """`from time import time`, `from random import random`, and
    `import random as rnd` are the common import styles — the rule must
    resolve calls through them, and must NOT flag a local variable that
    merely shares a module's name."""
    src = """
    import random as rnd
    from random import random
    from time import perf_counter, time


    def stamp():
        return time()


    def jitter():
        return random() + rnd.choice([1, 2])


    def fine():
        time = perf_counter  # local rebinding of an innocent callable
        return time()
    """
    report = lint_src(tmp_path, src, name="clock.py",
                      subdir="inference/serving")
    hits = rules_at(report, "determinism")
    lines = {h.line for h in hits}
    assert line_of(src, "return time()") in lines
    assert line_of(src, "random() + rnd.choice") in lines
    # random() and rnd.choice() are two findings on one line; the local
    # rebinding of the NAME `time` to perf_counter still flags (import-
    # map resolution is by binding name — a documented approximation),
    # but perf_counter called under its own name never would
    assert len(hits) == 4


def test_lock_snapshot_name_reuse_in_unrelated_class_is_quiet(tmp_path):
    """Snapshot discipline is enforced cross-module BY FIELD NAME; a
    class that initializes its OWN field with a reused name (`last`,
    `programs`) is private single-threaded state, not the guarded
    field, and must not be gated."""
    (tmp_path / "eng.py").write_text(textwrap.dedent("""
    class Eng:
        def __init__(self):
            self.last = {}  # dslint: guarded-by=snapshot

        def bad(self):
            return sorted(self.last.items())
    """))
    (tmp_path / "other.py").write_text(textwrap.dedent("""
    class Unrelated:
        def __init__(self):
            self.last = {}

        def fine(self):
            return sorted(self.last.items())
    """))
    report = run_lint([str(tmp_path)])
    hits = rules_at(report, "lock-snapshot")
    assert len(hits) == 1
    assert hits[0].path.endswith("eng.py")


# ---------------------------------------------------------------------------
# golden fixtures: comm-pairs (async collective start/done discipline)
# ---------------------------------------------------------------------------

def test_comm_start_done_clean_patterns(tmp_path):
    """The in-tree shapes stay quiet: list-comp start + drain loop,
    monolithic (no start), start+done in one statement, done in an
    enclosing block, and a try whose finally drains."""
    report = lint_src(tmp_path, """
    def bucketed(dist, bufs):
        handles = [dist.reduce_scatter_start(b) for b in bufs]
        return [dist.reduce_scatter_done(h) for h in handles]

    def drain_loop(dist, bufs):
        hs = [dist.all_gather_start(b) for b in bufs]
        out = []
        for h in hs:
            out.append(dist.all_gather_done(h))
        return out

    def one_liner(dist, x):
        return dist.reduce_scatter_done(dist.reduce_scatter_start(x))

    def branch_then_join(dist, x, fancy):
        h = dist.all_reduce_start(x)
        if fancy:
            x = x * 2
        return dist.all_reduce_done(h)

    def finally_drains(dist, x):
        h = dist.broadcast_start(x)
        try:
            x = x + 1
        finally:
            x = dist.broadcast_done(h)
        return x

    def not_a_collective(engine):
        engine.timer_start()  # no paired verb: out of scope
    """)
    assert not rules_at(report, "comm-start-done")


def test_comm_start_without_done_flagged(tmp_path):
    report = lint_src(tmp_path, """
    def leaky(dist, bufs):
        handles = [dist.reduce_scatter_start(b) for b in bufs]
        return handles
    """)
    hits = rules_at(report, "comm-start-done")
    assert len(hits) == 1
    assert "reduce_scatter_done" in hits[0].message


def test_comm_done_only_in_one_branch_flagged(tmp_path):
    """A done inside one arm of an if does not cover the other arm."""
    report = lint_src(tmp_path, """
    def half_drained(dist, x, flag):
        h = dist.all_gather_start(x)
        if flag:
            x = dist.all_gather_done(h)
        return x

    def both_arms_ok(dist, x, flag):
        h = dist.all_gather_start(x)
        if flag:
            x = dist.all_gather_done(h)
        else:
            x = dist.all_gather_done(h) * 2
        return x
    """)
    hits = rules_at(report, "comm-start-done")
    assert len(hits) == 1
    assert hits[0].func == "half_drained"


def test_comm_early_return_between_pair_flagged(tmp_path):
    report = lint_src(tmp_path, """
    def early_exit(dist, x, bad):
        h = dist.reduce_scatter_start(x)
        if bad:
            return None
        return dist.reduce_scatter_done(h)
    """)
    hits = rules_at(report, "comm-start-done")
    assert len(hits) == 1
    assert "return/raise" in hits[0].message


def test_comm_nested_def_done_does_not_count(tmp_path):
    """A done inside a nested def is deferred code, not execution on
    this path — the start is still unmatched."""
    report = lint_src(tmp_path, """
    def outer(dist, x):
        h = dist.all_to_all_start(x)

        def later():
            return dist.all_to_all_done(h)

        return later
    """)
    hits = rules_at(report, "comm-start-done")
    assert len(hits) == 1


def test_comm_start_done_pragma_and_catalog(tmp_path):
    """Intentional handle handoff is exempted with a reasoned pragma,
    and the rule is in the shipped catalog."""
    assert "comm-start-done" in RULES
    report = lint_src(tmp_path, """
    def handoff(dist, x):
        # dslint: ignore[comm-start-done] caller drains via AsyncHandle API
        return dist.reduce_scatter_start(x)
    """)
    assert not rules_at(report, "comm-start-done")
    assert report.suppressed
