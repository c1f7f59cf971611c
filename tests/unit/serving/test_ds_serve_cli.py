"""`bin/ds_serve` input robustness: malformed JSONL lines become per-request
error records + non-zero exit — never a traceback (and never a checkpoint
load when nothing valid remains)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def test_malformed_jsonl_error_records_nonzero_exit(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('not json at all\n'
                   '{"max_new_tokens": 4}\n'
                   '{"prompt_ids": "nope"}\n'
                   '{"prompt_ids": []}\n'
                   '{"text": "needs a tokenizer"}\n')
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--checkpoint", str(tmp_path / "never_loaded"),
         "--prompts", str(bad), "--cpu"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert "Traceback" not in r.stderr
    recs = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    assert len(recs) == 5 and all(rec["state"] == "error" for rec in recs)
    assert recs[0]["line"] == 1 and "Expecting value" in recs[0]["error"]
    assert "prompt_ids or text" in recs[1]["error"]
    assert "non-empty list" in recs[2]["error"]
    assert "tokenizer" in recs[4]["error"]


@pytest.mark.slow  # tracing covered fast in-process; demo CLI keeps
                   # replicas/admin-port as the subprocess representatives
def test_demo_trace_dir_writes_perfetto_trace_and_stats(tmp_path):
    """The observability acceptance path: a --demo --trace-dir run must
    leave a Perfetto-loadable trace with complete per-request timelines,
    and --stats-interval-s must put health lines on stderr (stdout stays
    pure result JSONL)."""
    trace_dir = tmp_path / "traces"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--demo", "4", "--cpu", "--trace-dir", str(trace_dir),
         "--stats-interval-s", "1"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    assert "[ds_serve] steps=" in r.stderr         # the health line
    assert "trace written:" in r.stderr
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.strip().startswith("{")]  # skip engine-init log lines
    final = recs[-1]
    trace_file = final["trace_file"]
    assert os.path.exists(trace_file)
    assert final["flight_dumps"] == []             # clean run: no incidents

    from deepspeed_tpu.monitor.tracing import validate_event

    doc = json.load(open(trace_file))
    evs = doc["traceEvents"]
    assert all(validate_event(e) is None for e in evs)
    # complete timelines: every demo request has a terminal umbrella span
    rids = {rec["rid"] for rec in recs if "rid" in rec}
    assert len(rids) == 4
    umbrellas = {(e.get("args") or {}).get("rid") for e in evs
                 if e["name"] == "request"}
    assert rids <= umbrellas


def test_admin_port_live_process_answers_control_plane(tmp_path):
    """The r11 acceptance path: a LIVE ``ds_serve --admin-port`` process
    must answer /metrics (valid Prometheus text, parsed here), /healthz,
    /readyz and /statusz while it serves. DS_FAULT=slow_step paces every
    step so the serving window is long enough to probe without racing
    the drain."""
    import socket
    import time
    import urllib.error
    import urllib.request

    from deepspeed_tpu.monitor.export import parse_prometheus

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--demo", "12", "--cpu", "--admin-port", str(port),
         "--ttft-slo-s", "60", "--tpot-slo-s", "60"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "DS_FAULT": "slow_step:seconds=0.05"})
    url = f"http://127.0.0.1:{port}"

    def get(path):
        try:
            r = urllib.request.urlopen(url + path, timeout=5)
            return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    try:
        # the server binds BEFORE the model loads: liveness within a few
        # seconds of process start, long before any token is served
        deadline = time.time() + 120
        while True:
            assert proc.poll() is None, \
                (proc.poll(), proc.communicate()[1][-2000:])
            try:
                code, _ = get("/healthz")
                break
            except (urllib.error.URLError, ConnectionError, OSError):
                assert time.time() < deadline, "admin server never bound"
                time.sleep(0.1)
        assert code == 200
        # poll /metrics until the engine is attached AND serving (steps
        # moving), all while the process lives
        while True:
            assert proc.poll() is None, \
                (proc.poll(), proc.communicate()[1][-2000:])
            code, text = get("/metrics")
            assert code == 200
            if text:
                series, types = parse_prometheus(text)  # must be valid
                if series.get(("ds_steps", frozenset()), 0) >= 1:
                    break
            assert time.time() < deadline, "engine never started serving"
            time.sleep(0.1)
        assert types["ds_ttft_s"] == "summary"
        assert series[("ds_compile_count",
                       frozenset({("program", "mixed_step")}))] == 1.0
        code, body = get("/readyz")
        assert code in (200, 503)  # cold until the first step compiles
        assert json.loads(body)["ok"] is (code == 200)
        code, body = get("/statusz")
        assert code == 200 and "mixed_step" in body
        out, err = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, err[-2000:]
    recs = [json.loads(ln) for ln in out.splitlines()
            if ln.strip().startswith("{")]
    final = recs[-1]
    # the final report records the SLO block and the admin endpoint
    assert final["slo"]["ttft_slo_s"] == 60.0
    verdicts = final["slo"]["verdicts"]
    assert sum(verdicts.values()) == 12 and verdicts["good"] == 12
    assert final["slo"]["goodput_tokens"] > 0
    assert final["admin"]["port"] == port
    assert final["admin"]["scrapes"] >= 1
    assert "goodput_tok/s=" not in out  # stats line stays on stderr


@pytest.mark.slow  # speculation covered fast by test_speculative.py
def test_spec_tokens_demo_reports_speculation(tmp_path):
    """--spec-tokens arms prompt-lookup speculation end to end through
    the CLI: the run serves, the stats line carries acceptance, and the
    final report's speculation block names the drafter and counters."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--demo", "6", "--cpu", "--spec-tokens", "4",
         "--max-new-tokens", "24", "--stats-interval-s", "1"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.strip().startswith("{")]
    final = lines[-1]
    spec = final["speculation"]
    assert spec["enabled"] and spec["drafter"] == "prompt_lookup"
    assert spec["spec_tokens"] == 4
    assert spec["drafted"] >= 0 and 0.0 <= spec["accept_rate"] <= 1.0
    assert final["serving_metrics"]["spec_drafted"] == spec["drafted"]
    assert final["serving_metrics"]["compile_counts"] == {"mixed_step": 1}
    assert "spec_acc=" in r.stderr, "stats line must carry acceptance"


@pytest.mark.slow  # tiers covered fast by test_kv_tiers.py
def test_host_cache_demo_reports_tier_table(tmp_path):
    """--host-cache-blocks end-to-end: the demo serves with the host
    spill tier armed (implying --prefix-cache), the stats line carries
    host_hit_rate/promote_q, and the final report's kv_tiers block
    lists both tiers with the movement counters."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--demo", "6", "--cpu", "--host-cache-blocks", "64",
         "--num-blocks", "32", "--stats-interval-s", "0.2"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    assert "host_hit_rate=" in r.stderr and "promote_q=" in r.stderr
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.strip().startswith("{")]
    final = recs[-1]
    tiers = final["kv_tiers"]
    assert tiers["enabled"] is True
    assert [t["tier"] for t in tiers["tiers"]] == ["device", "host"]
    assert tiers["tiers"][1]["capacity_blocks"] == 64
    snap = final["serving_metrics"]
    assert "kv_host_blocks" in snap and "host_hit_rate" in snap
    assert final["serving_metrics"]["compile_counts"] == {"mixed_step": 1}


def test_demo_cannot_mix_with_prompts(tmp_path):
    p = tmp_path / "p.jsonl"
    p.write_text('{"prompt_ids": [1]}\n')
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--demo", "2", "--prompts", str(p), "--cpu"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 2
    assert "cannot be combined" in r.stderr


def test_replicas_demo_serves_fleet_and_reports(tmp_path):
    """--replicas N serves through the ServingRouter end to end: every
    demo request finishes on some replica, the stats line is the fleet
    one, and the final report carries the fleet status (per-replica
    rows + router counters) instead of the single-engine blocks."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
         "--demo", "6", "--cpu", "--replicas", "2", "--prefix-cache",
         "--stats-interval-s", "1"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    assert "fleet steps=" in r.stderr
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.strip().startswith("{")]
    final = recs[-1]
    fleet = final["fleet"]
    assert len(fleet["replicas"]) == 2
    assert fleet["counters"]["requests_finished"] == 6
    assert set(final["replica_metrics"]) == {"r0", "r1"}
    results = [rec for rec in recs[:-1] if "rid" in rec]
    assert len(results) == 6
    assert all(rec["state"] == "finished" for rec in results)
    assert all(rec["served_on"] for rec in results)


@pytest.mark.slow  # journal + recovery covered fast in-process
                   # (test_journal.py, fleet recovery tests)
def test_journal_dir_demo_durable_and_restart_recovers_nothing(tmp_path):
    """--journal-dir serves through a journaled 1-replica fleet: the
    final report carries the journal block, records show recovered
    status, and a SECOND run on the same directory recovers nothing
    (everything terminal on disk) while still serving fresh traffic —
    the restart path end to end."""
    jdir = str(tmp_path / "journal")

    def run(n):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
             "--demo", str(n), "--cpu", "--journal-dir", jdir],
            capture_output=True, text=True, timeout=240, cwd=REPO)
        assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
        recs = [json.loads(ln) for ln in r.stdout.splitlines()
                if ln.strip().startswith("{")]
        return recs, r.stderr

    recs, err = run(3)
    final = recs[-1]
    j = final["fleet"]["journal"]
    assert j["dir"] == jdir
    assert j["non_terminal"] == 0          # everything landed terminal
    results = [rec for rec in recs[:-1] if "rid" in rec]
    assert len(results) == 3
    assert all(rec["state"] == "finished" and not rec["recovered"]
               for rec in results)

    recs2, err2 = run(2)
    assert "recovered" not in err2          # nothing live to recover
    final2 = recs2[-1]
    # the journal replayed the previous incarnation's records
    assert final2["fleet"]["journal"]["requests_tracked"] >= 3
    assert final2["fleet"]["counters"]["requests_recovered"] == 0
