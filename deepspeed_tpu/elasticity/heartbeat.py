"""Heartbeat protocol between the engine and the elastic agent's watchdog.

A wedged device (an outage of the retired backend had 108 probes in a row
hang past their 120 s cap) is the failure class the exit-code-only agent
cannot see: a rank stuck in a collective never exits, so the job stalls
forever.

Protocol: each worker writes ``<checkpoint_dir>/heartbeats/rank_<r>.json``
(``{"step", "time", "pid"}``) via temp-file + ``os.replace`` at the top of
every training step (interval configurable). The agent's watchdog reads the
files' mtimes: a rank whose heartbeat is older than ``timeout_s`` — counting
only heartbeats written since the current incarnation spawned — is a dead
worker, and the agent hard-kills the wedged tree and enters its normal
restart/resize/resume path.

Only ranks that have heartbeated AT LEAST TWICE in this incarnation are
judged: a script that never heartbeats (no engine) is simply not
watchdog-protected, and the window between a rank's first and second beat —
which contains the initial XLA compile, often minutes — can never trigger a
false kill-loop. Steady-state hangs (a rank wedging at step N) are exactly
the r5 outage class and are always caught.
"""

import itertools
import json
import os
import time
from typing import Dict, Optional

HEARTBEAT_SUBDIR = "heartbeats"

#: per-process write counter ("seq"): the watchdog judges a rank only from
#: its SECOND beat of an incarnation, so the window between beat 1 and
#: beat 2 — which contains the first XLA compile, often minutes — can never
#: trigger a false kill-loop on a healthy job
_SEQ = itertools.count(1)


def heartbeat_dir(checkpoint_dir: str) -> str:
    return os.path.join(checkpoint_dir, HEARTBEAT_SUBDIR)


def heartbeat_path(checkpoint_dir: str, rank: int) -> str:
    return os.path.join(heartbeat_dir(checkpoint_dir), f"rank_{rank}.json")


def write_heartbeat(checkpoint_dir: str, rank: int, step: int) -> None:
    """Atomic, best-effort: a full disk or flaky NFS must degrade to 'no
    watchdog protection', never to a crashed training step."""
    try:
        os.makedirs(heartbeat_dir(checkpoint_dir), exist_ok=True)
        path = heartbeat_path(checkpoint_dir, rank)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"step": int(step), "time": time.time(),
                       "pid": os.getpid(), "seq": next(_SEQ)}, f)
        os.replace(tmp, path)
    except OSError:
        pass


def read_heartbeats(checkpoint_dir: str) -> Dict[int, Dict]:
    """rank -> {step, time, pid, mtime} for every readable heartbeat file."""
    out: Dict[int, Dict] = {}
    hb_dir = heartbeat_dir(checkpoint_dir)
    try:
        names = os.listdir(hb_dir)
    except OSError:
        return out
    for name in names:
        if not (name.startswith("rank_") and name.endswith(".json")):
            continue
        path = os.path.join(hb_dir, name)
        try:
            rank = int(name[len("rank_"):-len(".json")])
            with open(path) as f:
                rec = json.load(f)
            rec["mtime"] = os.path.getmtime(path)
            out[rank] = rec
        except (OSError, ValueError):
            continue  # mid-replace / torn read: skip this poll
    return out


class HeartbeatMonitor:
    """The agent-side staleness watchdog for ONE incarnation.

    ``start()`` marks the spawn instant; ``check()`` returns a human-readable
    reason when some rank that heartbeated during this incarnation has gone
    stale past ``timeout_s`` (→ the agent should kill and restart), else
    None. ``timeout_s <= 0`` disables the watchdog entirely.
    """

    def __init__(self, checkpoint_dir: str, timeout_s: float):
        self.checkpoint_dir = checkpoint_dir
        self.timeout_s = float(timeout_s)
        self._spawn_t = time.time()

    def start(self) -> None:
        self._spawn_t = time.time()

    #: slack when deciding whether a heartbeat belongs to this incarnation:
    #: file mtimes come from a coarser clock than time.time() and can lag
    #: the spawn instant by a tick; incarnations are > 2s apart (reap +
    #: drain sleep), so 1s cannot misattribute a previous incarnation's beat
    SPAWN_SLACK_S = 1.0

    def check(self, now: Optional[float] = None) -> Optional[str]:
        if self.timeout_s <= 0:
            return None
        now = time.time() if now is None else now
        for rank, rec in sorted(read_heartbeats(self.checkpoint_dir).items()):
            # prefer the writer's own time.time() stamp (same clock as
            # _spawn_t); mtime is the fallback for torn/old records
            stamp = max(float(rec.get("time") or 0.0),
                        float(rec.get("mtime") or 0.0))
            if stamp < self._spawn_t - self.SPAWN_SLACK_S:
                continue  # previous incarnation's heartbeat
            if int(rec.get("seq") or 2) < 2:
                # a single beat means the rank is still inside its first
                # step — which contains the initial XLA compile; judging it
                # would kill-loop healthy jobs whose compile exceeds the
                # timeout. Steady-state hangs (beat >= 2) are the r5 class.
                continue
            age = now - stamp
            if age > self.timeout_s:
                return (f"rank {rank} heartbeat is {age:.0f}s old "
                        f"(step {rec.get('step')}, timeout "
                        f"{self.timeout_s:.0f}s) — worker wedged")
        return None
