"""Crash-safe write-ahead request journal for the serving fleet.

PR 11's router survives a *replica* kill, but the router process itself
was a single point of failure: a crash (or a deploy-time restart)
silently dropped every accepted request. This module gives the serving
stack the crash-safety story training already has (PR 1's verified
checkpoint manifests): every fleet admission is made DURABLE before the
door accepts it, progress and outcomes append as the request runs, and
``ServingRouter.recover`` replays the journal after process death —
re-admitting every non-terminal request carrying its delivered-token
watermark, exactly the recompute-resume semantics replica kills already
proved, lifted one level up.

Write-ahead discipline (the ordering IS the contract):

1. **admit** — appended and fsync'd BEFORE the fleet door accepts: a
   crash at any later point still knows the request existed;
2. **deliver** — the delivered-token watermark (token ids included),
   appended whenever a replica segment's output folds into the fleet
   record and fsync'd before the caller can observe those tokens — so a
   recovered request resumes at exactly the watermark and tokens are
   never delivered twice;
3. **terminal** — the request's outcome, fsync'd at the fleet-terminal
   transition: a finished request can never be re-served by recovery.

Records are one line each — ``<crc32 hex>:<payload json>\\n`` — so a
torn tail (kill -9 mid-append) is detected by checksum/shape and
TRUNCATED on recovery: at most the one in-flight record is lost, never
a committed one (the ``checkpoint/manifest.py`` torn-``latest`` idiom,
applied to an append-only log).

Segments rotate by size; :meth:`RequestJournal.compact` rewrites sealed
segments shedding a terminal request's payload records — its verdict
stays behind as a slim TOMBSTONE until the entry ages out of the
duplicate-suppression window (see :meth:`prune_terminal_state`), so the
door's retry suppression survives restarts — via temp + ``os.replace``
(the manifest's atomic-commit idiom: readers see the old segment or the
compacted one, never a half-write), deleting segments left empty. The
journal's footprint tracks the LIVE request set plus that bounded
tombstone window, not traffic volume.

**Scale events** (PR 17's elastic fleet) extend the same write-ahead
discipline to fleet MEMBERSHIP: every autoscaler transition journals an
``intent`` record (fsync'd) BEFORE the fleet acts and a ``done`` record
after, so a crash mid-transition recovers to a consistent replica set —
an unfinished scale-out leaves NO ghost replica (the intent is aborted
on recovery; capacity the fleet never acknowledged never existed), an
unfinished scale-in leaves the replica ACTIVE (its drain died with the
process; the requests it was shedding are themselves journaled and
recover independently). :attr:`RequestJournal.scale_state` is the
replayed fold: replica index -> desired membership + pending intent.
Scale records carry no fid, so compaction keeps only the LAST record
per replica (the fold is last-write-wins per index) and replay in an
older reader skips them — the vocabulary is forward-compatible by the
same rule as every other record type.
"""

import io
import json
import os
import threading
import time
import weakref
import zlib

try:
    import fcntl
except ImportError:          # non-POSIX: no cross-process writer lock
    fcntl = None  # type: ignore[assignment]
from dataclasses import dataclass, field
from typing import Any, Dict, IO, List, Optional

from ...utils.logging import log_dist, logger

#: segment filenames sort lexicographically == numerically (8 digits)
_SEG_PREFIX = "journal-"
_SEG_SUFFIX = ".wal"

#: durability syscall for appends: fdatasync flushes the data AND the
#: file size (everything replay needs) while skipping the timestamp
#: metadata commit fsync pays for — measurably cheaper tails on ext4.
#: Falls back to fsync where fdatasync does not exist (non-POSIX).
_datasync = getattr(os, "fdatasync", os.fsync)

#: live journals in this process (weak — a dropped journal vanishes);
#: ``ds_report``'s journal section reads from here, the same registry
#: pattern (and lock law) as the engine / router / admin-server sets
_live_journals_lock = threading.Lock()
_LIVE_JOURNALS: "weakref.WeakSet" = weakref.WeakSet()  # dslint: guarded-by=_live_journals_lock


def live_request_journals() -> List["RequestJournal"]:
    """Strong refs to every live RequestJournal in this process."""
    with _live_journals_lock:
        return list(_LIVE_JOURNALS)


class JournalCorruptionError(RuntimeError):
    """A committed (non-tail) journal record failed validation — bit rot
    or an outside writer, not a torn append."""


class JournalLockedError(RuntimeError):
    """The journal directory is owned by ANOTHER process's writer —
    opening it here would truncate the owner's in-flight append as a
    "torn tail" and race its compaction's ``os.replace``. An overlapping
    deploy must wait for (or kill) the old process before the new one
    opens the same ``--journal-dir``."""


#: shared empty payload marking a SLIMMED terminal entry (prompt/tokens
#: dropped by ``prune_terminal_state``; identity-checked so slimming is
#: idempotent and never allocates per entry)
_TOMBSTONE: List[int] = []


@dataclass
class JournalEntry:
    """Replayed state of ONE fleet request (folded over its records)."""

    fid: str
    prompt: List[int]
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    priority: int = 0
    #: absolute WALL-clock deadline (``time.time``; perf_counter stamps
    #: do not survive the process, deadlines must) — None = no deadline
    deadline_wall: Optional[float] = None
    submit_wall: float = 0.0
    #: tokens durably delivered to the caller, in order (the watermark a
    #: recovery resumes from; undelivered tokens regenerate)
    tokens: List[int] = field(default_factory=list)
    state: Optional[str] = None        # terminal state, None while live
    reason: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.state is not None


def _encode(payload: Dict[str, Any]) -> bytes:
    body = json.dumps(payload, separators=(",", ":"),
                      sort_keys=True).encode("utf-8")
    crc = zlib.crc32(body) & 0xFFFFFFFF
    return b"%08x:" % crc + body + b"\n"


def _decode(line: bytes) -> Optional[Dict[str, Any]]:
    """Parse one journal line; None = invalid (torn / corrupt)."""
    if not line.endswith(b"\n") or len(line) < 10 or line[8:9] != b":":
        return None
    body = line[9:-1]
    try:
        crc = int(line[:8], 16)
    except ValueError:
        return None
    if zlib.crc32(body) & 0xFFFFFFFF != crc:
        return None
    try:
        payload = json.loads(body)
    except ValueError:
        return None
    return payload if isinstance(payload, dict) else None


class RequestJournal:
    """Append-only, fsync'd, size-rotated request journal in one
    directory. Single-writer (the router thread) by design — replay and
    status are safe from anywhere, appends are not concurrent; a POSIX
    lock on ``<dir>/LOCK`` enforces the single writer ACROSS processes
    (:class:`JournalLockedError` on an overlapping open)."""

    def __init__(self, journal_dir: str, segment_bytes: int = 1 << 20):
        if segment_bytes < 4096:
            raise ValueError("segment_bytes must be >= 4096")
        self.dir = journal_dir
        self.segment_bytes = int(segment_bytes)
        os.makedirs(journal_dir, exist_ok=True)
        # single-writer exclusion ACROSS processes: a POSIX record lock
        # (lockf) on <dir>/LOCK, released by the OS on any death incl.
        # kill -9. POSIX locks are per-PROCESS, so a same-process reopen
        # — the simulated-crash recovery path tests and the chaos fuzzer
        # drive — is deliberately allowed (caveat: closing the abandoned
        # writer's LOCK fd drops the process's lock; exclusion degrades
        # only on that same-process path, never for a real deploy
        # overlap, which is two processes).
        self._lock_f: Optional[IO[bytes]] = None
        if fcntl is not None:
            lf = open(os.path.join(journal_dir, "LOCK"), "a+b")
            try:
                fcntl.lockf(lf.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                try:
                    lf.seek(0)
                    owner = lf.read(32).decode(errors="replace").strip()
                finally:
                    lf.close()
                raise JournalLockedError(
                    f"journal {journal_dir!r} is owned by another "
                    f"process (pid {owner or '?'}): wait for it to exit "
                    f"before opening this journal dir")
            lf.truncate(0)
            lf.write(str(os.getpid()).encode())
            lf.flush()
            self._lock_f = lf
        # sweep compaction temp files a crash orphaned (written but not
        # yet os.replace'd — the replace never happened, so the original
        # segment is intact and the temp is pure dead weight)
        for name in os.listdir(journal_dir):
            if name.startswith(_SEG_PREFIX) and ".tmp." in name:
                try:
                    os.remove(os.path.join(journal_dir, name))
                except OSError:
                    pass
        # monotone counters (the status block / ds_report row)
        self.appends = 0
        self.compactions = 0
        self.records_compacted = 0
        self.torn_tails_truncated = 0
        #: ``time.monotonic`` stamp of the last compaction (age in
        #: status); None = never ran in this process
        self._last_compaction: Optional[float] = None
        #: replayed + live state: fid -> JournalEntry (insertion order ==
        #: admit order — recovery re-admits in this order)
        self.state: "Dict[str, JournalEntry]" = {}
        #: replayed fleet-membership fold (the elastic-fleet contract):
        #: replica idx -> {"active": Optional[bool], "pending":
        #: Optional[op], "n": seq}. ``active`` None = the journal never
        #: closed a transition for this replica (base fleet membership
        #: governs); ``pending`` non-None = a crash interrupted a
        #: transition (``ServingRouter.recover`` reconciles: an
        #: unfinished scale-out aborts, an unfinished scale-in leaves
        #: the replica active)
        self.scale_state: Dict[int, Dict[str, Any]] = {}
        #: monotone scale-record sequence (stamped as ``n`` so compaction
        #: can tell a superseded record from the current one)
        self.scale_appends = 0
        #: per-replica ``n`` of the last CLOSING record (done/abort):
        #: older scale records are compactable
        self._scale_last_close: Dict[int, int] = {}
        #: segment indices holding any scale record (compaction dirty
        #: marking for membership records, which carry no fid)
        self._scale_segs: set = set()
        #: fid -> segment indices holding any of its records; feeds the
        #: dirty-segment set so compaction never re-reads a sealed
        #: segment with nothing to shed (without it every compact() is
        #: O(total journal bytes) on the router step loop)
        self._fid_segs: Dict[str, set] = {}
        #: sealed segments that MAY hold droppable records (a fid there
        #: turned terminal, or was pruned from the state). Marked at
        #: append_terminal/prune time, cleared after a compaction scan;
        #: everything starts dirty so the first compact of a reopened
        #: journal scans once.
        self._dirty_segs: set = set()
        self._recover_segments()
        segs = self._segments()
        self._dirty_segs = {self._index_of(p) for p in segs}
        self._active_idx = self._index_of(segs[-1]) if segs else 1
        self._active: Optional[IO[bytes]] = None
        self._active_size = os.path.getsize(self._seg_path(self._active_idx)) \
            if segs else 0
        #: True while sync=False appends are not yet on disk (flush()
        #: no-ops when clean, so the per-step flush is free in steady
        #: state)
        self._unsynced = False
        with _live_journals_lock:
            _LIVE_JOURNALS.add(self)
        log_dist(f"RequestJournal: {journal_dir} ({len(segs)} segment(s), "
                 f"{len(self.state)} replayed, "
                 f"{len(self.non_terminal())} live)", ranks=[0])

    # -- segment bookkeeping -------------------------------------------

    def _seg_path(self, idx: int) -> str:
        return os.path.join(self.dir, f"{_SEG_PREFIX}{idx:08d}{_SEG_SUFFIX}")

    @staticmethod
    def _index_of(path: str) -> int:
        name = os.path.basename(path)
        return int(name[len(_SEG_PREFIX):-len(_SEG_SUFFIX)])

    def _segments(self) -> List[str]:
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        out = [os.path.join(self.dir, n) for n in sorted(names)
               if n.startswith(_SEG_PREFIX) and n.endswith(_SEG_SUFFIX)]
        return out

    # -- append (the write-ahead path) ---------------------------------

    def _open_active(self) -> IO[bytes]:
        if self._active is None:
            self._active = open(self._seg_path(self._active_idx), "ab")
        return self._active

    def _rotate_if_needed(self) -> None:
        if self._active_size < self.segment_bytes:
            return
        if self._active is not None:
            self.flush()  # unsynced batched records must not die with
            self._active.close()  # the sealed segment's file handle
            self._active = None
        self._active_idx += 1
        self._active_size = 0

    def _append(self, payload: Dict[str, Any], sync: bool = True) -> None:
        """Append ONE record; with ``sync`` the bytes are on disk before
        this returns — the caller sequences this BEFORE the action the
        record makes durable."""
        self._rotate_if_needed()
        fid = payload.get("fid")
        if fid is not None:
            self._fid_segs.setdefault(fid, set()).add(self._active_idx)
        if payload.get("t") == "scale":
            self._scale_segs.add(self._active_idx)
        data = _encode(payload)
        f = self._open_active()
        f.write(data)
        f.flush()
        if sync:
            _datasync(f.fileno())
            self._unsynced = False
        else:
            self._unsynced = True
        self._active_size += len(data)
        self.appends += 1

    def flush(self) -> None:
        """fsync any records appended with ``sync=False`` (batched
        appends — e.g. a deliver record immediately followed by its
        terminal record pays ONE fsync for both; a sync append also
        flushes every earlier unsynced record on the same segment).
        No-op when nothing is pending."""
        if self._active is not None and self._unsynced:
            self._active.flush()
            _datasync(self._active.fileno())
            self._unsynced = False

    def knows(self, fid: str) -> bool:
        """Has this journal ever admitted ``fid``? (The door's duplicate
        suppression: an admit record is appended once per fid, ever.)"""
        return fid in self.state

    def append_admit(self, fid: str, prompt: List[int],
                     max_new_tokens: int,
                     eos_token_id: Optional[int] = None,
                     priority: int = 0,
                     deadline_wall: Optional[float] = None) -> None:
        """Make one admission durable (fsync'd) BEFORE the fleet door
        accepts it. Idempotent per fid: a duplicate admit (recovered
        request re-entering through recover, or a client retry) appends
        nothing."""
        if fid in self.state:
            return
        toks = [int(t) for t in prompt]
        ts = time.time()  # dslint: ignore[determinism] wall clock of record: journal stamps must survive the process, perf_counter does not
        # the record dict is encoded (and its bytes fsync'd) inside
        # _append, so the entry can own the same list — one copy on the
        # admission hot path, not two
        self._append({"t": "admit", "fid": fid,
                      "prompt": toks,
                      "new": int(max_new_tokens),
                      "eos": eos_token_id, "pri": int(priority),
                      "deadline": deadline_wall,
                      "ts": ts})
        self.state[fid] = JournalEntry(
            fid=fid, prompt=toks,
            max_new_tokens=int(max_new_tokens), eos_token_id=eos_token_id,
            priority=int(priority), deadline_wall=deadline_wall,
            submit_wall=ts)

    def append_deliver(self, fid: str, tokens: List[int],
                       sync: bool = True) -> None:
        """Record tokens delivered to the caller (the watermark). With
        ``sync`` the record is durable before the caller observes the
        tokens — the zero-duplicate-delivery half of recovery."""
        if not tokens:
            return
        ent = self.state.get(fid)
        if ent is None or ent.done:
            return  # unknown / already-terminal fid: nothing to watermark
        self._append({"t": "deliver", "fid": fid,
                      "tok": [int(t) for t in tokens]}, sync=sync)
        ent.tokens.extend(int(t) for t in tokens)

    def append_terminal(self, fid: str, terminal_state: str, reason: str,
                        sync: bool = True) -> None:
        """Record a request's fleet-terminal verdict (fsync'd): recovery
        will never re-serve it."""
        ent = self.state.get(fid)
        if ent is None or ent.done:
            return
        self._append({"t": "terminal", "fid": fid,
                      "state": terminal_state,
                      "reason": reason}, sync=sync)
        ent.state = terminal_state
        ent.reason = reason
        # move to the dict tail: terminals order by COMPLETION, so the
        # prune window keeps the newest-FINISHED entries (a long-lived
        # request that finishes now must not be forgotten before one
        # that finished long ago but was admitted later)
        self.state[fid] = self.state.pop(fid)
        # every segment holding this fid's payload records now has
        # something compaction can shed
        self._dirty_segs |= self._fid_segs.get(fid, set())

    def append_scale(self, op: str, replica: int, phase: str,
                     reason: str = "") -> None:
        """Make one fleet-membership transition durable (fsync'd). The
        WRITE-AHEAD half of the elastic-fleet contract: ``intent`` is on
        disk BEFORE the fleet acts (spawn/activate/drain/retire) and
        ``done`` only after the transition completed — so a crash at any
        point recovers to a consistent replica set: no ghost replicas
        (an unclosed scale-out aborts on recovery), no lost capacity
        (an unclosed scale-in leaves the replica active). ``abort``
        closes an intent without changing membership."""
        if op not in ("out", "in"):
            raise ValueError(f"scale op must be 'out' or 'in', got {op!r}")
        if phase not in ("intent", "done", "abort"):
            raise ValueError(f"scale phase must be intent|done|abort, "
                             f"got {phase!r}")
        payload = {"t": "scale", "op": op, "replica": int(replica),
                   "phase": phase, "reason": reason,
                   "n": self.scale_appends,
                   "ts": time.time()}  # dslint: ignore[determinism] wall clock of record: journal stamps must survive the process, perf_counter does not
        self._append(payload)
        self._fold(payload)
        if phase in ("done", "abort"):
            # every scale record older than this closing one is now
            # compactable (last-write-wins per replica index)
            self._dirty_segs |= self._scale_segs

    # -- replay / recovery ---------------------------------------------

    def _recover_segments(self, truncate_torn: bool = True) -> None:
        """Replay every segment into :attr:`state`, truncating a torn
        tail in the FINAL segment (kill -9 mid-append: the only place a
        half-written record can exist — appends are sequential and
        fsync'd, rotation only ever opens a fresh file). An invalid line
        in a SEALED segment is corruption, not a torn append, and
        raises — silently skipping committed records would turn bit rot
        into silent request loss. ``truncate_torn=False`` skips the
        repair write (:func:`replay_journal`'s read-only contract)."""
        segs = self._segments()
        for i, path in enumerate(segs):
            last = i == len(segs) - 1
            idx = self._index_of(path)
            good_bytes = 0
            try:
                with open(path, "rb") as f:
                    # ONE read snapshot: sizes and contents below refer
                    # to the same bytes even if a live owner replaces or
                    # deletes the file under a read-only replay
                    data = f.read()
            except FileNotFoundError:
                if truncate_torn:
                    raise  # the OWNER's own segment cannot vanish
                # read-only replay racing the live owner's compact():
                # the emptied segment was deleted between our listing
                # and this open — its records were all shed (terminal
                # or pruned); nothing to fold
                continue
            for line in io.BytesIO(data):
                payload = _decode(line)
                if payload is None:
                    if not last:
                        raise JournalCorruptionError(
                            f"invalid record in sealed journal "
                            f"segment {path} at byte {good_bytes} "
                            f"(not a torn tail; refusing to guess)")
                    break
                self._fold(payload)
                fid = payload.get("fid")
                if fid is not None:
                    self._fid_segs.setdefault(fid, set()).add(idx)
                if payload.get("t") == "scale":
                    self._scale_segs.add(idx)
                good_bytes += len(line)
            if last and good_bytes < len(data):
                if not truncate_torn:
                    # read-only replay: the "torn tail" may simply be a
                    # LIVE writer's in-flight append — repairing it here
                    # would corrupt the active journal under its owner.
                    # Ignore it; the owning journal repairs on reopen.
                    continue
                lost = len(data) - good_bytes
                logger.error(f"journal: torn tail in {path} — truncating "
                             f"{lost} byte(s) (at most the in-flight "
                             f"record is lost)")
                with open(path, "r+b") as f:
                    f.truncate(good_bytes)
                    f.flush()
                    os.fsync(f.fileno())
                self.torn_tails_truncated += 1

    def _fold(self, payload: Dict[str, Any]) -> None:
        t = payload.get("t")
        fid = payload.get("fid")
        if t == "admit" and fid is not None:
            prev = self.state.get(fid)
            if prev is None or prev.done:
                # a second admit record for a TERMINAL fid is a NEW
                # incarnation (the rid was retried after its entry aged
                # past the prune hard cap, so the door re-admitted):
                # reset the entry — otherwise the first incarnation's
                # terminal record would mask the live retry on replay,
                # silently losing it across a crash. (Replacement keeps
                # the dict's first-insert position; live fids never see
                # a second admit — the door suppresses them.)
                self.state[fid] = JournalEntry(
                    fid=fid, prompt=list(payload.get("prompt", [])),
                    max_new_tokens=int(payload.get("new", 1)),
                    eos_token_id=payload.get("eos"),
                    priority=int(payload.get("pri", 0)),
                    deadline_wall=payload.get("deadline"),
                    submit_wall=float(payload.get("ts", 0.0)))
        elif t == "deliver":
            ent = self.state.get(fid)
            if ent is not None and not ent.done:
                ent.tokens.extend(int(x) for x in payload.get("tok", []))
        elif t == "terminal":
            ent = self.state.get(fid)
            if ent is None:
                if fid is not None:
                    # a compacted segment's terminal TOMBSTONE (payload
                    # records shed, the verdict kept): rebuild the
                    # slimmed entry so the door's duplicate suppression
                    # survives a restart — without it a client retry of
                    # a compacted terminal would re-admit and re-serve
                    # (the double delivery the door exists to prevent)
                    self.state[fid] = JournalEntry(
                        fid=fid, prompt=_TOMBSTONE, max_new_tokens=0,
                        tokens=_TOMBSTONE, state=payload.get("state"),
                        reason=payload.get("reason"))
            else:
                # LAST terminal wins — the log is chronological, and a
                # done entry here can be an EARLIER incarnation's
                # verdict (its re-admit record shed by compaction, its
                # own terminal kept as a tombstone): the later record
                # is the true final state, not a duplicate to ignore
                ent.state = payload.get("state")
                ent.reason = payload.get("reason")
                # replay is chronological, so moving to the tail on the
                # terminal transition reproduces completion order — the
                # same invariant append_terminal keeps live
                self.state[fid] = self.state.pop(fid)
        elif t == "scale":
            ridx = payload.get("replica")
            if not isinstance(ridx, int):
                return  # malformed membership record: skip, never guess
            n = payload.get("n")
            n = self.scale_appends if not isinstance(n, int) else n
            self.scale_appends = max(self.scale_appends, n + 1)
            st = self.scale_state.setdefault(
                ridx, {"active": None, "pending": None, "n": -1})
            st["n"] = n
            phase = payload.get("phase")
            if phase == "intent":
                st["pending"] = payload.get("op")
            elif phase == "done":
                st["active"] = payload.get("op") == "out"
                st["pending"] = None
                self._scale_last_close[ridx] = n
            elif phase == "abort":
                st["pending"] = None
                self._scale_last_close[ridx] = n
        # unknown record types are skipped: a newer writer's vocabulary
        # must not brick an older reader's recovery

    def non_terminal(self) -> List[JournalEntry]:
        """Every request the journal admitted but never saw finish —
        what :meth:`ServingRouter.recover` re-admits, in admit order."""
        return [e for e in self.state.values() if not e.done]

    # -- compaction ----------------------------------------------------

    def compact(self) -> int:
        """Shed TERMINAL requests' payload records (admit/deliver) from
        sealed segments, keeping each one's terminal verdict as a slim
        TOMBSTONE while its entry is still in :attr:`state` — replay
        rebuilds the slimmed entry from it, so the door's duplicate
        suppression spans restarts with the same window as
        ``prune_terminal_state`` (a compacted-away terminal would
        otherwise re-admit on a client retry, delivering twice).
        Records of fids PRUNED from the state drop entirely. A sealed
        segment left empty is deleted; one with survivors is rewritten
        via temp + ``os.replace`` (readers see the old segment or the
        compacted one, never a torn half — the manifest atomic-commit
        idiom). The active segment is never touched (it is mid-append).
        Returns records dropped."""
        dropped = 0
        for path in self._segments():
            idx = self._index_of(path)
            if idx >= self._active_idx:
                continue  # active (or future): mid-append, leave it
            if idx not in self._dirty_segs:
                # no fid with records here turned terminal (or was
                # pruned) since the last scan: nothing droppable, skip
                # the read entirely
                continue
            keep: List[bytes] = []
            total = 0
            seen_fids: set = set()
            kept_fids: set = set()
            kept_scale = False
            with open(path, "rb") as f:
                for line in f:
                    total += 1
                    payload = _decode(line)
                    if payload is None:
                        raise JournalCorruptionError(
                            f"invalid record in sealed journal segment "
                            f"{path} during compaction")
                    fid = payload.get("fid")
                    if payload.get("t") == "scale":
                        # fleet-membership record: last-write-wins per
                        # replica index. A closing record (done/abort)
                        # supersedes everything older for its replica,
                        # so keep only records at or past the last
                        # close — that is the closing record itself
                        # plus any NEWER intent (an open transition
                        # must survive for recovery to reconcile it).
                        # Malformed shapes keep verbatim: not ours to
                        # judge, mirroring the unknown-type rule.
                        ridx = payload.get("replica")
                        n = payload.get("n")
                        if (isinstance(ridx, int) and isinstance(n, int)
                                and n < self._scale_last_close.get(
                                    ridx, -1)):
                            continue
                        keep.append(line)
                        kept_scale = True
                        continue
                    if payload.get("t") not in ("admit", "deliver",
                                                "terminal") or fid is None:
                        # a newer writer's record vocabulary (or an
                        # fid-less record shape): not ours to judge —
                        # keep it verbatim, mirroring _fold's skip
                        # rule, so an older-version compactor never
                        # erases what a newer reader still needs
                        keep.append(line)
                        if fid is not None:
                            seen_fids.add(fid)
                            kept_fids.add(fid)
                        continue
                    seen_fids.add(fid)
                    ent = self.state.get(fid)
                    if ent is None:
                        # PRUNED from the in-memory state, which only
                        # ever forgets terminal entries: dead weight
                        # (keeping unknown-fid records would make
                        # segments whose requests outlived the prune
                        # window immortal)
                        continue
                    if ent.done:
                        # terminal: shed the payload records, keep the
                        # verdict as the duplicate-suppression tombstone
                        if payload.get("t") == "terminal":
                            keep.append(line)
                            kept_fids.add(fid)
                        continue
                    keep.append(line)
                    if fid is not None:
                        kept_fids.add(fid)
            self._dirty_segs.discard(idx)
            if not kept_scale:
                self._scale_segs.discard(idx)
            if len(keep) == total:
                continue
            for fid in seen_fids - kept_fids:
                s = self._fid_segs.get(fid)
                if s is not None:
                    s.discard(idx)
                    if not s:
                        del self._fid_segs[fid]
            dropped += total - len(keep)
            if not keep:
                os.remove(path)
            else:
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.writelines(keep)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
        if dropped:
            self.compactions += 1
            self.records_compacted += dropped
        self._last_compaction = time.monotonic()
        return dropped

    def prune_terminal_state(self, keep: int = 4096,
                             hard_cap: int = 65536) -> None:
        """Bound the in-memory replay state on a long-lived router:
        terminal entries beyond the newest ``keep`` are SLIMMED (prompt
        and token payloads dropped; fid + terminal verdict stay, so the
        door's duplicate suppression and compaction both keep working),
        and only entries beyond ``hard_cap`` are forgotten entirely —
        the duplicate-suppression window is therefore the newest
        ``hard_cap`` terminals, at ~100 bytes each. "Newest" is
        COMPLETION order: entries move to the dict tail on their
        terminal transition, so a just-finished long-runner is never
        forgotten before requests that finished long ago."""
        done = [fid for fid, e in self.state.items() if e.done]
        for fid in done[:max(0, len(done) - hard_cap)]:
            # the forgotten fid's on-disk records (its tombstone, and
            # any payload records compaction has not reached yet) are
            # now droppable
            self._dirty_segs |= self._fid_segs.pop(fid, set())
            del self.state[fid]
        for fid in done[max(0, len(done) - hard_cap):
                        max(0, len(done) - keep)]:
            ent = self.state.get(fid)
            if ent is not None and ent.tokens is not _TOMBSTONE:
                ent.prompt = _TOMBSTONE
                ent.tokens = _TOMBSTONE

    # -- status / lifecycle --------------------------------------------

    def status(self) -> Dict[str, Any]:
        """One status block (fleet /statusz, ds_report, ds_serve final
        report): directory, segment count/bytes, live vs terminal
        records, compaction recency."""
        segs = self._segments()
        size = 0
        for p in segs:
            try:
                size += os.path.getsize(p)
            except OSError:
                pass
        # snapshot first: the admin scrape thread calls this while the
        # router thread mutates state (insert/move-to-tail/prune) — an
        # iterator over the live dict would intermittently raise
        # "dictionary changed size during iteration" mid-scrape
        entries = list(self.state.values())
        live = sum(1 for e in entries if not e.done)
        return {
            "dir": self.dir,
            "segments": len(segs),
            "bytes": size,
            "records_appended": self.appends,
            "requests_tracked": len(entries),
            "non_terminal": live,
            "compactions": self.compactions,
            "records_compacted": self.records_compacted,
            "scale_records": self.scale_appends,
            "scale_replicas_tracked": len(self.scale_state),
            "torn_tails_truncated": self.torn_tails_truncated,
            "last_compaction_age_s":
                None if self._last_compaction is None
                else round(time.monotonic() - self._last_compaction, 3),
        }

    def close(self) -> None:
        if self._active is not None:
            self.flush()
            self._active.close()
            self._active = None
        if self._lock_f is not None:
            try:
                self._lock_f.close()   # releases the writer lock
            except OSError:
                pass
            self._lock_f = None


def replay_journal(journal_dir: str) -> Dict[str, JournalEntry]:
    """STRICTLY read-only replay of a journal directory: no torn-tail
    repair (a "torn tail" may be a live writer's in-flight append — the
    owning journal truncates on ITS reopen), no open segment, no write
    of any kind — safe to run against a journal another process is
    actively appending to. The convergence check tools
    (``tools/chaos_fuzz.py``) and tests compare a live fleet's terminal
    set against exactly this."""
    j = RequestJournal.__new__(RequestJournal)
    j.dir = journal_dir
    j.segment_bytes = 1 << 20
    j.appends = 0
    j.compactions = 0
    j.records_compacted = 0
    j.torn_tails_truncated = 0
    j._last_compaction = None
    j.state = {}
    j._fid_segs = {}
    j._dirty_segs = set()
    j.scale_state = {}
    j.scale_appends = 0
    j._scale_last_close = {}
    j._scale_segs = set()
    j._recover_segments(truncate_torn=False)
    return j.state


def replay_scale_state(journal_dir: str) -> Dict[int, Dict[str, Any]]:
    """Read-only fold of the fleet-membership (scale) records, same
    no-write contract as :func:`replay_journal`. The chaos fuzzer
    compares a recovered fleet's replica set against exactly this:
    ``active`` is True (scaled out), False (scaled in) or None (base
    membership governs); ``pending`` non-None means the journal ends
    mid-transition — recovery must have reconciled (aborted) it."""
    j = RequestJournal.__new__(RequestJournal)
    j.dir = journal_dir
    j.segment_bytes = 1 << 20
    j.appends = 0
    j.compactions = 0
    j.records_compacted = 0
    j.torn_tails_truncated = 0
    j._last_compaction = None
    j.state = {}
    j._fid_segs = {}
    j._dirty_segs = set()
    j.scale_state = {}
    j.scale_appends = 0
    j._scale_last_close = {}
    j._scale_segs = set()
    j._recover_segments(truncate_torn=False)
    return j.scale_state
