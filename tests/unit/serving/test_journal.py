"""Durable serving: crash-safe request journal + fleet restart recovery.

The journal's contract, pinned here:

- **write-ahead ordering** — admit fsync'd before the door accepts,
  delivery watermark before the caller observes tokens, terminal verdict
  at the fleet-terminal funnel;
- **torn-tail recovery** — kill -9 mid-append (a SIGKILLed subprocess,
  and parametrized byte-offset truncations) loses at most the one
  in-flight record, NEVER a committed one, and recovery truncates the
  tail instead of refusing the segment;
- **restart recovery** — ``ServingRouter.recover`` re-admits every
  non-terminal request at its delivered-token watermark: greedy token
  identity with an undisturbed run, zero duplicate deliveries, zero
  leaked pages, terminal-set convergence between the live router and the
  on-disk replay;
- **rolling restart** — every replica drained → killed → revived one at
  a time, fleet capacity never below the floor, requests never notice
  beyond latency.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import (RequestJournal, RouterConfig,
                                             ServingConfig,
                                             JournalCorruptionError,
                                             init_fleet, replay_journal)
from deepspeed_tpu.inference.serving.journal import _SEG_PREFIX

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

MAX_STEPS = 600
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

VOCAB = None


@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    global VOCAB
    cfg = LlamaConfig.tiny(remat=False)
    VOCAB = cfg.vocab_size
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    return ds.init_inference(model, params=params, dtype="fp32")


def fleet(engine, n=2, jdir=None, rcfg_kw=None, **scfg_kw):
    scfg = dict(max_batch_size=2, block_size=8, num_blocks=48,
                max_model_len=96, prefix_cache=True)
    scfg.update(scfg_kw)
    rkw = dict(journal_dir=jdir)
    rkw.update(rcfg_kw or {})
    return init_fleet(engine, n, serving_config=ServingConfig(**scfg),
                      router_config=RouterConfig(**rkw))


# ---------------------------------------------------------------------------
# journal unit: append / replay / rotation / compaction
# ---------------------------------------------------------------------------

def test_roundtrip_rotation_and_compaction(tmp_path, capsys):
    d = str(tmp_path / "j")
    j = RequestJournal(d, segment_bytes=4096)
    # ds_report's journal line reads the live journal's status()
    from deepspeed_tpu.env_report import journal_report
    journal_report()
    assert f"request journal: {d}" in capsys.readouterr().out
    for i in range(60):
        j.append_admit(f"r{i}", list(range(30)), 8, eos_token_id=5,
                       priority=i % 3, deadline_wall=None)
        if i % 3 != 2:
            j.append_deliver(f"r{i}", [i, i + 1])
            j.append_terminal(f"r{i}", "finished", "length")
    j.close()
    assert len(j._segments()) > 1  # size rotation happened

    # replay from scratch reconstructs exactly the folded state
    st = replay_journal(d)
    assert len(st) == 60
    assert st["r0"].done and st["r0"].tokens == [0, 1]
    assert st["r0"].eos_token_id == 5
    assert not st["r2"].done and st["r2"].tokens == []

    # compaction: sealed segments shed terminal records atomically;
    # every LIVE record survives and replay is unchanged for them
    j2 = RequestJournal(d, segment_bytes=4096)
    dropped = j2.compact()
    assert dropped > 0
    st2 = replay_journal(d)
    live = {f for f, e in st2.items() if not e.done}
    assert live == {f"r{i}" for i in range(60) if i % 3 == 2}
    # duplicate admits append nothing (idempotent per fid)
    appends0 = j2.appends
    j2.append_admit("r2", [9, 9], 8)
    assert j2.appends == appends0
    j2.close()


def test_prune_slims_then_caps_and_compaction_still_drops(tmp_path):
    """prune_terminal_state SLIMS old terminal entries (payloads
    dropped, fid + verdict kept — duplicate suppression and compaction
    keep working) and forgets them only past the hard cap; compaction
    drops records whose fid was pruned entirely (only terminal entries
    are ever pruned, so an unknown fid is a dead record, not a live
    one — without this, segments outliving the prune window would be
    immortal)."""
    d = str(tmp_path / "j")
    j = RequestJournal(d, segment_bytes=4096)
    for i in range(30):
        j.append_admit(f"r{i}", list(range(30)), 4)
        j.append_terminal(f"r{i}", "finished", "length")
    j.prune_terminal_state(keep=10, hard_cap=20)
    assert len(j.state) == 20                  # hard cap forgets r0..r9
    assert not j.knows("r5") and j.knows("r15") and j.knows("r29")
    assert j.state["r15"].tokens == [] and j.state["r15"].done  # slimmed
    dropped = j.compact()
    assert dropped > 0
    # records of the FORGOTTEN fids are gone from disk too
    st = replay_journal(d)
    assert "r5" not in st
    j.close()

    # re-admitting a fid whose entry aged past the hard cap starts a
    # NEW incarnation: with BOTH incarnations' records still on disk
    # (no compaction ran), replay must yield the live retry — not the
    # first incarnation's stale terminal verdict masking it
    d2 = str(tmp_path / "j2")
    j2 = RequestJournal(d2)
    j2.append_admit("x", [1, 2, 3], 4)
    j2.append_terminal("x", "finished", "length")
    j2.prune_terminal_state(keep=0, hard_cap=0)   # forgotten entirely
    assert not j2.knows("x")
    j2.append_admit("x", [7, 7, 7], 4)            # the retry
    j2.close()
    st2 = replay_journal(d2)
    assert not st2["x"].done and st2["x"].prompt == [7, 7, 7]


def test_prune_window_is_completion_ordered(tmp_path):
    """The duplicate-suppression window keeps the newest-FINISHED
    terminals, not the earliest-admitted: a long-runner admitted first
    but finished just now must outlive requests that finished long ago
    (entries move to the dict tail on their terminal transition — live
    and on replay alike)."""
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    j.append_admit("long", [1], 4)                    # admitted FIRST
    for i in range(5):
        j.append_admit(f"r{i}", [1], 4)
        j.append_terminal(f"r{i}", "finished", "length")
    j.append_terminal("long", "finished", "length")   # finishes LAST
    j.prune_terminal_state(keep=0, hard_cap=3)
    assert j.knows("long") and j.knows("r4") and j.knows("r3")
    assert not j.knows("r0") and not j.knows("r2")
    j.close()
    # replay (chronological fold) reproduces the same completion order
    j2 = RequestJournal(d)
    j2.prune_terminal_state(keep=0, hard_cap=3)
    assert j2.knows("long") and j2.knows("r4") and not j2.knows("r0")
    j2.close()


def test_compaction_keeps_terminal_tombstones_across_restart(tmp_path):
    """Compaction sheds a terminal request's payload records but keeps
    its verdict as a TOMBSTONE while the entry is in the suppression
    window: a restarted journal still ``knows`` the fid (a client retry
    after the restart suppresses instead of re-serving). Once the entry
    ages past the hard cap, a fresh compaction drops the tombstone too —
    the on-disk window matches the in-memory one."""
    d = str(tmp_path / "j")
    j = RequestJournal(d, segment_bytes=4096)
    for i in range(60):
        j.append_admit(f"r{i}", list(range(30)), 8)
        j.append_deliver(f"r{i}", [i])
        j.append_terminal(f"r{i}", "finished", "length")
    assert len(j._segments()) > 1
    assert j.compact() > 0
    j.close()
    # restart: replay rebuilds SLIMMED terminal entries from the kept
    # tombstones (r0 lived in a compacted sealed segment)
    j2 = RequestJournal(d, segment_bytes=4096)
    assert j2.knows("r0") and j2.state["r0"].done
    assert j2.state["r0"].tokens == []   # payloads shed with the records
    # pruned past the hard cap -> the tombstones compact away as well
    j2.prune_terminal_state(keep=0, hard_cap=0)
    j2.compact()
    j2.close()
    assert "r0" not in replay_journal(d)


def test_replay_journal_is_read_only_on_torn_tail(tmp_path):
    """``replay_journal`` is a diagnostic read that may run against a
    journal another process is ACTIVELY appending to: a torn tail (which
    may simply be the live writer's in-flight record) must be ignored,
    never repaired in place — truncating under the owner's open handle
    would garble its next append. The owning journal's reopen repairs."""
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    j.append_admit("a", [1, 2], 4)
    j.append_admit("b", [3, 4], 4)
    j.close()
    path = j._segments()[-1]
    with open(path, "ab") as f:
        f.write(b"00000000:{\"t\"")      # a live writer's half-append
    size = os.path.getsize(path)
    st = replay_journal(d)
    assert set(st) == {"a", "b"}         # committed records replay fine
    assert os.path.getsize(path) == size  # NO write side effect
    j2 = RequestJournal(d)               # the owner still repairs
    assert j2.torn_tails_truncated == 1
    assert os.path.getsize(path) < size
    j2.close()


@pytest.mark.parametrize("cut_back", [1, 7, 19])
def test_torn_tail_truncated_at_byte_offsets(tmp_path, cut_back):
    """Truncate the final segment mid-record at several byte offsets:
    recovery drops AT MOST the record the cut landed in, never a
    committed one, and repairs the file in place."""
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    for i in range(10):
        j.append_admit(f"r{i}", list(range(8)), 4)
    j.close()
    path = j._segments()[-1]
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - cut_back)  # tear inside the LAST record
    j2 = RequestJournal(d)
    assert j2.torn_tails_truncated == 1
    # r0..r8 are committed records and MUST survive; r9 held the cut
    for i in range(9):
        assert f"r{i}" in j2.state
    assert "r9" not in j2.state
    # the repaired file replays clean (idempotent recovery)
    j3 = RequestJournal(d)
    assert j3.torn_tails_truncated == 0
    assert len(j3.state) == 9


def test_garbage_tail_truncated_and_sealed_corruption_raises(tmp_path):
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    j.append_admit("a", [1, 2], 4)
    j.close()
    path = j._segments()[-1]
    with open(path, "ab") as f:
        f.write(b"deadbeef:{not json")  # torn mid-append, no newline
    j2 = RequestJournal(d)
    assert j2.torn_tails_truncated == 1 and "a" in j2.state

    # a bad record in a SEALED segment is corruption, not a torn tail:
    # recovery must refuse loudly instead of silently dropping requests
    j3 = RequestJournal(d, segment_bytes=4096)
    for i in range(80):
        j3.append_admit(f"s{i}", list(range(30)), 4)
    j3.close()
    sealed = j3._segments()[0]
    assert os.path.basename(sealed).startswith(_SEG_PREFIX)
    with open(sealed, "r+b") as f:
        f.seek(20)
        f.write(b"\x00CORRUPT\x00")
    with pytest.raises(JournalCorruptionError, match="sealed"):
        RequestJournal(d, segment_bytes=4096)


@pytest.mark.parametrize("confirm_at", [5, 40])
def test_subprocess_kill9_mid_append_loses_no_committed_record(
        tmp_path, confirm_at):
    """The real thing: a writer subprocess appending in a tight loop is
    SIGKILLed at a (traffic-dependent, effectively random) byte offset.
    Every record the child CONFIRMED (printed after its fsync returned)
    must survive recovery; the torn tail — if the kill landed mid-append
    — is truncated without complaint."""
    d = str(tmp_path / "j")
    child_src = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from deepspeed_tpu.inference.serving.journal import "
        "RequestJournal\n"
        "j = RequestJournal(sys.argv[1], segment_bytes=1 << 14)\n"
        "i = 0\n"
        "while True:\n"
        "    j.append_admit(f'r{i}', list(range(32)), 4)\n"
        "    print(f'r{i}', flush=True)\n"
        "    i += 1\n")
    proc = subprocess.Popen([sys.executable, "-c", child_src, d],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)
    confirmed = []
    deadline = time.time() + 60
    try:
        while len(confirmed) < confirm_at:
            line = proc.stdout.readline().strip()
            if line.startswith("r") and line[1:].isdigit():
                confirmed.append(line)  # (skips the logger's own lines)
            assert time.time() < deadline, "journal writer child wedged"
    finally:
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
    st = replay_journal(d)
    missing = [fid for fid in confirmed if fid not in st]
    assert not missing, f"kill -9 lost CONFIRMED records: {missing}"
    # and the journal reopens for appending (tail repaired, if any)
    j = RequestJournal(d, segment_bytes=1 << 14)
    j.append_admit("after", [1], 4)
    j.close()
    assert "after" in replay_journal(d)


def test_second_writer_excluded_cross_process(tmp_path):
    """Cross-process single-writer exclusion: while one PROCESS owns a
    journal dir, another process's open raises JournalLockedError — an
    overlapping deploy's second writer would otherwise truncate the
    owner's in-flight append as a "torn tail" and race its compaction's
    os.replace. A SAME-process reopen (the simulated-crash recovery path
    tests and the chaos fuzzer drive) stays allowed: POSIX record locks
    are per-process, and the OS releases them on any death incl.
    kill -9."""
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    j.append_admit("a", [1, 2], 4)
    child_src = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from deepspeed_tpu.inference.serving.journal import (\n"
        "    JournalLockedError, RequestJournal)\n"
        "try:\n"
        "    RequestJournal(sys.argv[1])\n"
        "except JournalLockedError:\n"
        "    sys.exit(42)\n"
        "sys.exit(1)\n")
    rc = subprocess.run([sys.executable, "-c", child_src, d],
                        stdout=subprocess.DEVNULL,
                        stderr=subprocess.DEVNULL).returncode
    assert rc == 42, "second process opened a LOCKED journal"
    # same-process reopen: allowed (abandon-without-close = crash sim)
    j2 = RequestJournal(d)
    assert j2.knows("a")
    j2.close()
    j.close()
    # with every owner gone the lock is free again
    rc = subprocess.run([sys.executable, "-c", child_src, d],
                        stdout=subprocess.DEVNULL,
                        stderr=subprocess.DEVNULL).returncode
    assert rc == 1   # child opened fine, exited via sys.exit(1)


def test_status_safe_against_concurrent_transitions(tmp_path):
    """status() is scrape-thread-safe: it snapshots the state dict
    before counting, so a scrape racing the router thread's transitions
    (admit inserts, terminal move-to-tail, prune deletes) never raises
    "dictionary changed size during iteration" — the law
    ServingRouter.status() promises the admin /statusz thread."""
    import threading

    d = str(tmp_path / "j")
    j = RequestJournal(d)
    stop = threading.Event()

    def mutate():
        i = 0
        while not stop.is_set():
            j.append_admit(f"m{i}", [1], 2)
            j.append_terminal(f"m{i}", "finished", "length", sync=False)
            if i % 97 == 0:
                j.prune_terminal_state(keep=8, hard_cap=16)
            i += 1

    t = threading.Thread(target=mutate, daemon=True)
    t.start()
    try:
        deadline = time.time() + 1.0
        while time.time() < deadline:
            s = j.status()   # must never RuntimeError mid-iteration
            assert s["requests_tracked"] >= 0
    finally:
        stop.set()
        t.join()
    j.close()


# ---------------------------------------------------------------------------
# router recovery
# ---------------------------------------------------------------------------

def test_crash_recovery_token_identity_and_convergence(engine, tmp_path):
    """The acceptance drill, in-process: crash the router mid-traffic
    (some requests finished, some mid-flight), recover a COLD fleet from
    the journal, and require greedy token identity with an undisturbed
    run, zero duplicate deliveries (journal watermark == delivered
    stream), zero leaks, and live/disk terminal-set convergence."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, VOCAB, int(rs.randint(6, 14)))
               for _ in range(8)]

    ref = fleet(engine, 2)
    ref_fids = [ref.submit(p, max_new_tokens=8) for p in prompts]
    ref_outs = ref.run(max_steps=MAX_STEPS)
    ref_tokens = [ref_outs[f].tokens for f in ref_fids]
    assert all(ref_outs[f].state == "finished" for f in ref_fids)

    jdir = str(tmp_path / "j")
    r1 = fleet(engine, 2, jdir=jdir)
    fids = [r1.submit(p, max_new_tokens=8) for p in prompts]
    # step until SOME requests finished and some are mid-flight, so the
    # crash catches both terminal records and live watermarks
    steps = 0
    while r1.metrics.requests_finished < 2:
        r1.step()
        steps += 1
        assert steps < MAX_STEPS
    assert r1.has_work()  # genuinely mid-traffic
    pre_crash = {f: r1.poll(f) for f in fids}
    r1.journal.close()
    del r1  # process death: every non-journaled byte is gone

    r2 = fleet(engine, 2, jdir=jdir)
    recovered = r2.recover()
    assert recovered, "nothing recovered from a mid-traffic crash"
    outs = r2.run(max_steps=MAX_STEPS)
    assert all(outs[f].state == "finished" for f in fids), \
        {f: outs[f].state for f in fids}
    # greedy token identity across the kill, per submission index
    assert [outs[f].tokens for f in fids] == ref_tokens
    # requests that finished BEFORE the crash report their original
    # stream (zero duplicate deliveries: nothing is re-served)
    for f in fids:
        if pre_crash[f].state == "finished":
            assert f not in recovered  # never re-admitted, never re-served
            assert outs[f].tokens == pre_crash[f].tokens
        else:
            assert outs[f].recovered
    # zero leaked pages fleet-wide, both incarnations' accounting clean
    r2.check_consistent()
    for rep in r2.replicas:
        assert rep.engine.block_pool.used_count == 0
    # journal replay converges to the live terminal set, watermark ==
    # delivered stream for every finished request
    disk = replay_journal(jdir)
    assert all(e.done for e in disk.values())
    for f in fids:
        assert disk[f].state == "finished"
        assert disk[f].tokens == outs[f].tokens
    # fresh traffic serves after recovery
    nf = r2.submit([3, 5, 7], max_new_tokens=2)
    assert r2.run(max_steps=MAX_STEPS)[nf].state == "finished"


def test_recover_deadline_expired_during_outage(engine, tmp_path):
    jdir = str(tmp_path / "j")
    r1 = fleet(engine, 1, jdir=jdir)
    dead = r1.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.05)
    alive = r1.submit([4, 5, 6], max_new_tokens=4)
    r1.journal.close()
    del r1
    time.sleep(0.2)  # the outage outlives the deadline
    r2 = fleet(engine, 1, jdir=jdir)
    recovered = r2.recover()
    assert recovered == [alive]  # the expired one never re-queues
    assert r2.poll(dead).state == "timeout"
    outs = r2.run(max_steps=MAX_STEPS)
    assert outs[alive].state == "finished"
    disk = replay_journal(jdir)
    assert disk[dead].state == "timeout"
    assert disk[alive].state == "finished"


def test_duplicate_rid_suppressed_at_door(engine, tmp_path):
    """A client retrying its submit after a router restart must not
    double-admit (live OR finished rid) — and a finished rid's retry
    returns the original outcome, not a second serving."""
    jdir = str(tmp_path / "j")
    r1 = fleet(engine, 1, jdir=jdir)
    fid = r1.submit([2, 4, 6, 8], max_new_tokens=4)
    outs = r1.run(max_steps=MAX_STEPS)
    tokens = outs[fid].tokens
    r1.journal.close()
    del r1

    r2 = fleet(engine, 1, jdir=jdir)
    r2.recover()
    # retry of the FINISHED request: suppressed, original outcome stands
    assert r2.submit([2, 4, 6, 8], max_new_tokens=4, rid=fid) == fid
    assert r2.metrics.duplicates_suppressed == 1
    out = r2.poll(fid)
    assert out.state == "finished" and out.tokens == tokens
    assert not r2.has_work()  # nothing was re-admitted
    # retry of a LIVE request: same suppression
    live = r2.submit([1, 3, 5], max_new_tokens=2, rid="client-key-1")
    assert live == "client-key-1"
    assert r2.submit([1, 3, 5], max_new_tokens=2,
                     rid="client-key-1") == live
    assert r2.metrics.requests_submitted == 1
    r2.run(max_steps=MAX_STEPS)


def test_door_materializes_journal_known_rid_for_poll(engine, tmp_path):
    """A suppressed retry must return an id the router can ANSWER for:
    a rid only the journal knows (retry after forget(), or after a
    restart before recover()) is materialized at the door — poll() never
    KeyErrors on an id submit() just handed back."""
    jdir = str(tmp_path / "j")
    r1 = fleet(engine, 1, jdir=jdir)
    fid = r1.submit([2, 4, 6, 8], max_new_tokens=4)
    tokens = r1.run(max_steps=MAX_STEPS)[fid].tokens
    # forget() released the record; the journal still knows the rid
    r1.forget(fid)
    assert fid not in r1._requests
    assert r1.submit([2, 4, 6, 8], max_new_tokens=4, rid=fid) == fid
    out = r1.poll(fid)   # must answer, not KeyError
    assert out.state == "finished" and out.tokens == tokens
    assert not r1.has_work()
    # a NON-terminal journal-known rid retried after a restart BEFORE
    # recover(): the retry re-admits it at its watermark (single-entry
    # recovery), and the router serves it
    ck = r1.submit([1, 3, 5, 7], max_new_tokens=3, rid="client-key-9")
    r1.journal.close()
    del r1
    r2 = fleet(engine, 1, jdir=jdir)   # no recover() call
    assert r2.submit([1, 3, 5, 7], max_new_tokens=3,
                     rid="client-key-9") == ck
    assert r2.metrics.duplicates_suppressed == 1
    assert r2.has_work()               # re-admitted, not dropped
    outs = r2.run(max_steps=MAX_STEPS)
    assert outs[ck].state == "finished" and outs[ck].recovered


def test_recover_degrades_unknown_terminal_vocabulary(engine, tmp_path):
    """A journaled terminal state this build's RequestState enum doesn't
    know (deploy rolled back across a vocabulary change — journal._fold
    keeps unknown states verbatim for exactly this case) must DEGRADE at
    materialization, not abort recovery: the entry surfaces as FAILED
    with the foreign verdict in the reason, is never re-served, and
    every other journaled request still recovers."""
    jdir = str(tmp_path / "j")
    j = RequestJournal(jdir)
    j.append_admit("newer", [2, 4, 6], 4)
    j.append_terminal("newer", "paused-v99", "preempted")  # foreign state
    j.append_admit("live", [1, 3, 5], 3)                   # must recover
    j.close()

    r = fleet(engine, 1, jdir=jdir)
    recovered = r.recover()
    assert recovered == ["live"]            # recovery was NOT aborted
    out = r.poll("newer")
    assert out.state == "failed" and out.recovered
    assert out.finish_reason == "journal-state:paused-v99"
    # suppressed at the door like any other terminal — never re-served
    assert r.submit([2, 4, 6], max_new_tokens=4, rid="newer") == "newer"
    outs = r.run(max_steps=MAX_STEPS)
    assert outs["live"].state == "finished" and outs["live"].recovered


def test_replay_journal_tolerates_vanished_segment(engine, tmp_path,
                                                   monkeypatch):
    """Read-only replay racing a live owner's compact(): a segment
    deleted between the directory listing and the open is skipped (its
    records were all shed), never a crash."""
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    j.append_admit("a", [1, 2, 3], 4)
    j.append_terminal("a", "finished", "length")
    j.append_admit("b", [4, 5, 6], 4)
    j.close()
    ghost = os.path.join(d, f"{_SEG_PREFIX}00000000.wal")
    real_segments = RequestJournal._segments

    def with_ghost(self):
        return [ghost] + real_segments(self)

    monkeypatch.setattr(RequestJournal, "_segments", with_ghost)
    st = replay_journal(d)   # must not FileNotFoundError on the ghost
    assert st["a"].done and not st["b"].done


def test_compact_skips_clean_segments(tmp_path):
    """Compaction is incremental: a sealed segment is re-read only when
    a fid with records there turned terminal (or was pruned) since the
    last scan — not O(total journal bytes) on every router step."""
    d = str(tmp_path / "j")
    j = RequestJournal(d, segment_bytes=4096)
    for i in range(60):
        j.append_admit(f"r{i}", list(range(30)), 8)
        if i < 30:
            j.append_terminal(f"r{i}", "finished", "length")
    assert len(j._segments()) > 2
    assert j.compact() > 0
    sealed = {j._index_of(p) for p in j._segments()
              if j._index_of(p) < j._active_idx}
    assert not (j._dirty_segs & sealed)     # every sealed segment clean
    # a clean pass opens NO segment files (shadow the module's builtin
    # open; restored in finally)
    import builtins
    opens = []
    mod_globals = RequestJournal.compact.__globals__

    def counting_open(*a, **k):
        opens.append(a[0])
        return builtins.open(*a, **k)

    mod_globals["open"] = counting_open
    try:
        assert j.compact() == 0
    finally:
        del mod_globals["open"]
    assert opens == []
    # a live fid turning terminal re-dirties exactly its segments...
    j.append_terminal("r45", "finished", "length")
    assert j._dirty_segs & j._fid_segs["r45"]
    assert j.compact() > 0               # r45's payload records shed
    # ...and pruning tombstoned fids re-dirties their segments too
    j.prune_terminal_state(keep=0, hard_cap=0)
    assert j.compact() > 0               # tombstones dropped
    live = {f for f, e in replay_journal(d).items() if not e.done}
    assert live == {f"r{i}" for i in range(30, 60) if i != 45}
    j.close()


def test_replay_last_terminal_wins_across_incarnations(tmp_path):
    """Two terminal records for one fid can both survive on disk (an
    earlier incarnation's tombstone outlives compaction; the re-admit
    record between them is shed): replay must report the LAST verdict —
    the log is chronological — not resurrect the first."""
    d = str(tmp_path / "j")
    j = RequestJournal(d, segment_bytes=4096)
    j.append_admit("x", [1, 2, 3], 4)
    j.append_terminal("x", "failed", "watchdog")       # incarnation 1
    j.prune_terminal_state(keep=0, hard_cap=0)         # aged out
    j.append_admit("x", [1, 2, 3], 4)                  # the retry
    j.append_deliver("x", [7, 8])
    j.append_terminal("x", "finished", "length")       # incarnation 2
    # seal the segment so compaction can shed the retry's payload
    # records, leaving ONLY the two terminal records for x
    for i in range(60):
        j.append_admit(f"pad{i}", list(range(30)), 4)
    assert len(j._segments()) > 1
    assert j.compact() > 0
    st = replay_journal(d)
    assert st["x"].done and st["x"].state == "finished"
    j.close()


def test_compact_keeps_unknown_record_vocabulary(tmp_path):
    """An older-version compactor must not erase a newer writer's
    records (mirrors _fold's skip rule): unknown record types survive
    compaction verbatim."""
    d = str(tmp_path / "j")
    j = RequestJournal(d, segment_bytes=4096)
    j.append_admit("a", [1, 2], 4)
    j.append_terminal("a", "finished", "length")
    j._append({"t": "lease", "fid": "a", "owner": "r0"})   # future vocab
    j._append({"t": "epoch", "n": 3})                      # fid-less
    for i in range(60):                                    # seal it
        j.append_admit(f"pad{i}", list(range(30)), 4)
    assert j.compact() > 0            # a's admit payload was shed...
    first = j._seg_path(1)
    with open(first, "rb") as f:
        body = f.read()
    assert b'"lease"' in body and b'"epoch"' in body   # ...these not
    replay_journal(d)                 # and replay still skips them
    j.close()


def test_submit_wall_set_on_live_append_and_replay(tmp_path):
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    j.append_admit("a", [1, 2], 4)
    live = j.state["a"].submit_wall
    assert live > 0
    j.close()
    assert replay_journal(d)["a"].submit_wall == live


def test_fleet_request_fid_is_required():
    """The fid default factory was dead code that bypassed _fresh_fid's
    journal-collision skip — constructing without an fid must fail."""
    from deepspeed_tpu.inference.serving.router import FleetRequest
    with pytest.raises(TypeError):
        FleetRequest(prompt=[1, 2], max_new_tokens=4)


def test_recovered_flag_rides_terminal_span(engine, tmp_path):
    jdir = str(tmp_path / "j")
    r1 = fleet(engine, 1, jdir=jdir)
    fid = r1.submit([1, 2, 3, 4], max_new_tokens=6)
    r1.journal.close()
    del r1
    r2 = fleet(engine, 1, jdir=jdir, trace=True)
    assert r2.recover() == [fid]
    outs = r2.run(max_steps=MAX_STEPS)
    assert outs[fid].state == "finished" and outs[fid].recovered
    spans = [e for e in r2.replicas[0].engine.tracer.events()
             if e.get("name") == "request"]
    assert spans and all(s["args"].get("recovered") for s in spans)


def test_fresh_fids_skip_recovered_namespace(engine, tmp_path,
                                             monkeypatch):
    """A restarted router's auto-fid counter restarts at 0 while the
    journal still holds the previous incarnation's fleet-N ids — new
    submits must SKIP those (and be journaled under their own ids)
    instead of silently colliding with recovered records."""
    import itertools

    from deepspeed_tpu.inference.serving import router as router_mod

    jdir = str(tmp_path / "j")
    r1 = fleet(engine, 1, jdir=jdir)
    old = [r1.submit([2, 4, 6], max_new_tokens=2) for _ in range(2)]
    r1.run(max_steps=MAX_STEPS)
    old_tokens = [r1.poll(f).tokens for f in old]
    r1.journal.close()
    del r1

    # a fresh process: the module-level counter restarts at zero
    monkeypatch.setattr(router_mod, "_fid_counter", itertools.count())
    r2 = fleet(engine, 1, jdir=jdir)
    r2.recover()
    new = r2.submit([1, 3, 5], max_new_tokens=2)
    assert new not in old              # no collision with recovered ids
    assert r2.journal.knows(new)       # the new request IS journaled
    outs = r2.run(max_steps=MAX_STEPS)
    assert outs[new].state == "finished"
    for f, toks in zip(old, old_tokens):
        assert outs[f].tokens == toks  # recovered records untouched
    disk = replay_journal(jdir)
    assert disk[new].tokens == outs[new].tokens
    # client rids may not squat the reserved auto-fid namespace
    with pytest.raises(ValueError, match="reserved"):
        r2.submit([7, 8], max_new_tokens=2, rid="fleet-999")


def test_recover_capacity_mismatch_fails_terminal_not_wedged(
        engine, tmp_path):
    """A request journaled by a bigger-configured incarnation that NO
    replica of the restarted fleet can hold must fail terminal
    (reason=capacity) instead of wedging the FIFO fleet queue."""
    jdir = str(tmp_path / "j")
    big = fleet(engine, 1, jdir=jdir, max_model_len=96)
    too_big = big.submit(list(range(1, 60)), max_new_tokens=20)
    fits = big.submit([1, 2, 3], max_new_tokens=4)
    big.journal.close()
    del big

    small = fleet(engine, 1, jdir=jdir, max_model_len=48, num_blocks=24)
    recovered = small.recover()
    assert recovered == [fits]
    assert small.poll(too_big).state == "failed"
    assert small.poll(too_big).finish_reason == "capacity"
    outs = small.run(max_steps=MAX_STEPS)
    assert outs[fits].state == "finished"     # the queue never wedged
    assert replay_journal(jdir)[too_big].state == "failed"


# ---------------------------------------------------------------------------
# rolling restart
# ---------------------------------------------------------------------------

def test_rolling_restart_drill(engine, tmp_path):
    """Every replica restarted one at a time mid-traffic: requests all
    finish (shed work re-serves elsewhere), capacity never drops below
    the floor, every replica comes back routable and COLD (prefix index
    dropped), fresh traffic serves after."""
    router = fleet(engine, 3, jdir=str(tmp_path / "j"))
    floor = 2
    min_alive = [len(router.replicas)]
    orig_kill = router.kill_replica

    def watched_kill(idx, reason="replica_kill"):
        out = orig_kill(idx, reason)
        min_alive[0] = min(min_alive[0],
                           sum(r.alive for r in router.replicas))
        return out

    router.kill_replica = watched_kill
    rs = np.random.RandomState(5)
    fids = [router.submit(rs.randint(1, VOCAB, 8), max_new_tokens=12)
            for _ in range(9)]
    for _ in range(3):
        router.step()
    res = router.rolling_restart(capacity_floor=floor)
    assert res["restarted"] == [r.name for r in router.replicas]
    assert min_alive[0] >= floor  # capacity floor held throughout
    outs = router.run(max_steps=MAX_STEPS)
    assert all(outs[f].state == "finished" for f in fids), \
        {f: outs[f].state for f in fids}
    assert router.metrics.rolling_restarts == 1
    for rep in router.replicas:
        assert rep.alive and rep.routable and rep.kills == 1
    router.check_consistent()
    nf = router.submit([3, 5, 7], max_new_tokens=2)
    assert router.run(max_steps=MAX_STEPS)[nf].state == "finished"


def test_rolling_restart_floor_validation(engine):
    router = fleet(engine, 2)
    with pytest.raises(ValueError, match="capacity_floor"):
        router.rolling_restart(capacity_floor=2)


# ---------------------------------------------------------------------------
# DS_FAULT=router_crash (the chaos-vocabulary process kill)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_router_crash_subprocess_kill_and_recover(tmp_path):
    """The full drill as a real process death: a child serving fleet is
    killed by ``DS_FAULT=router_crash`` (os._exit — kill -9 semantics,
    nothing flushed beyond the journal's fsyncs) mid-traffic; the parent
    recovers from the journal and every request finishes with greedy
    token identity vs the child's own undisturbed pass."""
    jdir = str(tmp_path / "j")
    child_src = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['DS_FAULT'] = "
        "'router_crash:step=6:tag=serving_fleet'\n"
        "import numpy as np, jax, jax.numpy as jnp\n"
        "import deepspeed_tpu as ds\n"
        "from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM\n"
        "from deepspeed_tpu.inference.serving import (RouterConfig, "
        "ServingConfig, init_fleet)\n"
        "cfg = LlamaConfig.tiny(remat=False)\n"
        "model = LlamaForCausalLM(cfg)\n"
        "params = jax.jit(model.init)(jax.random.PRNGKey(0), "
        "jnp.zeros((1, 8), jnp.int32))['params']\n"
        "engine = ds.init_inference(model, params=params, dtype='fp32')\n"
        "router = init_fleet(engine, 2, serving_config=ServingConfig("
        "max_batch_size=2, block_size=8, num_blocks=48, max_model_len=96,"
        " prefix_cache=True), router_config=RouterConfig("
        f"journal_dir={jdir!r}))\n"
        "rs = np.random.RandomState(11)\n"
        "for _ in range(6):\n"
        "    router.submit(rs.randint(1, cfg.vocab_size, 8), "
        "max_new_tokens=8)\n"
        "router.run(max_steps=600)\n"
        "sys.exit(3)  # unreachable: the crash fires at step 6\n")
    r = subprocess.run([sys.executable, "-c", child_src],
                       capture_output=True, text=True, timeout=300)
    from deepspeed_tpu.utils.fault_injection import CRASH_EXIT_CODE

    assert r.returncode == CRASH_EXIT_CODE, (r.returncode, r.stderr[-800:])

    # parent: recover from the journal and serve everything to the end
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ds.init_inference(model, params=params, dtype="fp32")
    router = init_fleet(eng, 2, serving_config=ServingConfig(
        max_batch_size=2, block_size=8, num_blocks=48, max_model_len=96,
        prefix_cache=True),
        router_config=RouterConfig(journal_dir=jdir))
    recovered = router.recover()
    assert recovered
    outs = router.run(max_steps=MAX_STEPS)
    disk = replay_journal(jdir)
    assert all(e.done for e in disk.values())
    # identity vs an undisturbed run of the same seeded traffic
    ref = init_fleet(eng, 2, serving_config=ServingConfig(
        max_batch_size=2, block_size=8, num_blocks=48, max_model_len=96,
        prefix_cache=True))
    rs = np.random.RandomState(11)
    ref_fids = [ref.submit(rs.randint(1, cfg.vocab_size, 8),
                           max_new_tokens=8) for _ in range(6)]
    ref_outs = ref.run(max_steps=MAX_STEPS)
    got = [disk[f].tokens if disk[f].state == "finished" else None
           for f in sorted(disk, key=lambda f: int(f.split("-")[-1]))]
    want = [ref_outs[f].tokens for f in ref_fids]
    assert got == want, (got, want)
    assert all(o.state == "finished" for o in outs.values())
    router.check_consistent()
