"""Environment / op-compatibility report (``ds_report``).

Counterpart of ``deepspeed/env_report.py`` (op install/compat matrix :140).
Run: ``python -m deepspeed_tpu.env_report``.
"""

import os
import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def op_report():
    from op_builder import ALL_OPS

    print("-" * 60)
    print("native op name" + "." * 16 + "compatible" + "." * 6 + "built")
    print("-" * 60)
    for name, builder in ALL_OPS.items():
        compatible = builder.is_compatible()
        built = os.path.exists(builder.lib_path())
        print(f"{name:<30}{GREEN_OK if compatible else RED_NO:<20}"
              f"{GREEN_OK if built else '[not built]'}")
    print("-" * 60)


def env_info():
    import jax
    import jaxlib

    import deepspeed_tpu

    print(f"deepspeed_tpu version: {deepspeed_tpu.__version__}")
    print(f"python version: {sys.version.split()[0]}")
    print(f"jax version: {jax.__version__}; jaxlib: {jaxlib.__version__}")
    # bounded device query: an accelerator runtime that does not answer
    # must not hang the report
    import threading

    result = {}

    def query():
        try:
            devs = jax.devices()
            result["msg"] = (f"devices: {len(devs)} x {devs[0].device_kind} "
                             f"(platform {devs[0].platform})")
        except Exception as e:  # no accelerator in this context
            result["msg"] = f"devices: unavailable ({e})"

    t = threading.Thread(target=query, daemon=True)
    t.start()
    t.join(timeout=float(os.environ.get("DS_REPORT_DEVICE_TIMEOUT", "20")))
    print(result.get("msg", "devices: query timed out (accelerator runtime "
                            "unreachable); set JAX_PLATFORMS=cpu to skip"))
    try:
        import flax
        import optax
        import orbax.checkpoint

        print(f"flax {flax.__version__}, optax {optax.__version__}")
    except Exception:
        pass


def fault_report() -> None:
    """Print the active ``DS_FAULT`` spec (parsed), so a chaos run's logs
    are self-describing: ds_report output pasted into an incident doc says
    exactly which faults were armed."""
    from deepspeed_tpu.utils import fault_injection

    raw = os.environ.get(fault_injection.ENV_VAR)
    if not raw:
        print("fault injection (DS_FAULT): none")
        return
    try:
        specs = fault_injection.parse_faults(raw)
    except ValueError as e:
        print(f"fault injection (DS_FAULT): {raw!r} MALFORMED — {e}")
        return
    print(f"fault injection (DS_FAULT): {raw}")
    for s in specs:
        params = ", ".join(f"{k}={v}"
                           for k, v in sorted(s.params.items())) or \
            "unconditional"
        print(f"  armed: {s.name} ({params})")


def trace_report() -> None:
    """Print tracing / flight-recorder status next to the DS_FAULT spec:
    an incident doc that records which faults were armed should also say
    where the post-mortems went (or that none were being captured)."""
    from deepspeed_tpu.monitor import tracing

    d = os.environ.get(tracing.ENV_TRACE_DIR)
    if not d:
        print(f"tracing ({tracing.ENV_TRACE_DIR}): disabled — no trace "
              f"ring, no flight recorder (set {tracing.ENV_TRACE_DIR}="
              f"/path to arm both)")
        return
    print(f"tracing ({tracing.ENV_TRACE_DIR}): armed -> {d}")
    if not os.path.isdir(d):
        print("  (directory not created yet; appears on first dump)")
        return
    # newest by mtime: filenames lead with the trigger slug, so a
    # lexicographic sort would order by incident TYPE, not recency
    def _mtime(n):
        try:
            return os.path.getmtime(os.path.join(d, n))
        except OSError:
            return 0.0

    names = sorted(os.listdir(d), key=_mtime)
    flights = [n for n in names
               if n.startswith("flight_") and n.endswith(".jsonl")]
    traces = [n for n in names
              if n.startswith("trace_") and n.endswith(".json")]
    print(f"  flight-recorder dumps: {len(flights)}"
          + (f" (newest: {flights[-1]})" if flights else ""))
    print(f"  trace files: {len(traces)}"
          + (f" (newest: {traces[-1]})" if traces else ""))


def admin_report() -> None:
    """Admin control-plane status (``monitor/export.py``): every live
    admin server in THIS process with its port and last-scrape recency.
    A fresh ``ds_report`` CLI run has no servers (they live inside
    serving processes) — call from in-process (or a test) to see them."""
    import time

    from deepspeed_tpu.monitor.export import live_admin_servers

    servers = live_admin_servers()
    if not servers:
        print("admin endpoints: none live in this process "
              "(ds_serve --admin-port N serves /metrics /healthz /readyz "
              "/statusz /profilez)")
        return
    now = time.time()
    for s in servers:
        if s.last_scrape_time is None:
            scrape = "never scraped"
        else:
            scrape = (f"last /metrics scrape {now - s.last_scrape_time:.1f}s "
                      f"ago ({s.scrape_count} total)")
        print(f"admin endpoints: {s.url} — {scrape}")


def comm_report() -> None:
    """Per-collective comm-tracing table (``comm/comm.py``): when
    ``configure_comm_tracing`` armed a registry and collectives ran, the
    op/dtype/bytes-bucket histograms print here — which collectives a
    run stages, how big, and their span-time distribution."""
    from deepspeed_tpu.comm.comm import comm_observer
    from deepspeed_tpu.monitor.export import split_key
    from deepspeed_tpu.monitor.registry import Histogram

    reg = comm_observer.registry
    rows = []
    if reg is not None:
        for key, metric in reg.items():
            name, labels = split_key(key)
            if name == "comm_op_s" and isinstance(metric, Histogram) \
                    and metric.count:
                rows.append((labels.get("op", "?"),
                             labels.get("dtype", "?"),
                             labels.get("bytes_bucket", "?"), metric))
    if not rows:
        if comm_observer.enabled:
            print("comm tracing: armed, no collectives recorded yet")
        return  # disabled and empty: stay silent like the op table
    print("-" * 60)
    print(f"{'collective':<20}{'dtype':<10}{'bytes':>10}{'count':>8}"
          f"{'p50':>10}{'p95':>10}")
    for op, dtype, bucket, h in sorted(rows):
        print(f"{op:<20}{dtype:<10}{bucket:>10}{h.count:>8}"
              f"{h.percentile(0.5) * 1e6:>9.1f}u"
              f"{h.percentile(0.95) * 1e6:>9.1f}u")


def dslint_report() -> None:
    """dslint static-analysis status: rule count, baseline size,
    ignore-pragma count, and a fresh-run verdict over the installed
    package (the gate itself lives in ``tools/dslint.py`` / tier-1; this
    section makes an incident doc say whether the tree it ran from was
    clean). Pure AST — no accelerator, well under a second."""
    import deepspeed_tpu
    from deepspeed_tpu.utils.lint_rules import lint_status

    pkg = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    baseline = os.path.join(os.path.dirname(pkg), "tools",
                            "dslint_baseline.json")
    try:
        st = lint_status(pkg, baseline_path=baseline
                         if os.path.exists(baseline) else None)
    except Exception as e:  # a broken linter must not break ds_report
        print(f"dslint: unavailable ({type(e).__name__}: {e})")
        return
    badge = GREEN_OK if st["findings"] == 0 else RED_NO
    print(f"dslint: {badge} {st['verdict']} — {st['rules']} rules over "
          f"{st['files']} files; baseline {st['baseline_entries']} "
          f"entr(ies) ({st['baselined']} matched), "
          f"{st['ignore_pragmas']} ignore pragma(s) in tree")


def perf_report() -> None:
    """Performance-accounting status (``monitor/perf.py``): per-device
    memory stats and the resident compiled-program table (name,
    fingerprint hash, compile/recompile counts, FLOPs a call and their
    source; under the train step's row its matrix work by scope).

    The program table is per-process — a fresh ``ds_report`` CLI run has
    no engines, so it reports none; call this from inside a serving or
    training process (or a test) to see the live table."""
    from deepspeed_tpu.monitor import perf

    print("-" * 60)
    stats = perf.device_memory_stats()
    if not stats:
        print("device memory stats: none exposed by this backend (CPU has "
              "no allocator stats; TPU reports live/peak HBM here)")
    else:
        print(f"{'device':<10}{'kind':<16}{'in_use':>12}{'peak':>12}"
              f"{'limit':>12}")
        for s in stats:
            fmt = lambda k: f"{s[k] / 1e9:.2f}G" if k in s else "n/a"
            print(f"{s['device']:<10}{s['kind']:<16}"
                  f"{fmt('bytes_in_use'):>12}{fmt('peak_bytes_in_use'):>12}"
                  f"{fmt('bytes_limit'):>12}")
    rows = perf.live_program_table()
    if not rows:
        print("compiled programs: none resident in this process")
        return
    from deepspeed_tpu.monitor.export import (LEDGER_HEADER, ledger_columns,
                                              memory_line, step_cost_line)

    print(f"{'program':<34}{'fingerprint':<13}{'compiles':>9}"
          f"{'recompiles':>11}{'calls':>7}{LEDGER_HEADER}  flops/call")
    for r in rows:
        flops = "n/a" if r["flops"] is None else f"{r['flops']:.3e}"
        print(f"{r['name']:<34}{str(r['fingerprint']):<13}"
              f"{r['compiles']:>9}{r['recompiles']:>11}{r['calls']:>7}"
              f"{ledger_columns(r)}  {flops} ({r['cost_source'] or '-'})")
        for line in filter(None, (memory_line(r), step_cost_line(r))):
            print(line)


def speculation_report() -> None:
    """Speculative-decoding status of every live ServingEngine in this
    process (drafter kind, draft cap, rolling accept rate) — printed
    next to the compiled-program table, which is per-process for the
    same reason: a fresh ``ds_report`` CLI run has no engines; call from
    inside a serving process (or a test) to see them."""
    from deepspeed_tpu.inference.serving import live_serving_engines

    engines = live_serving_engines()
    if not engines:
        return  # nothing to report; stay silent like the program table
    for srv in engines:
        st = srv.speculation_status()
        if not st["enabled"]:
            print("speculation: off (ServingConfig.spec_tokens=0)")
            continue
        print(f"speculation: {st['drafter']} k<={st['spec_tokens']} — "
              f"drafted {st['drafted']}, accepted {st['accepted']} "
              f"(accept rate {st['accept_rate']:.2f}, "
              f"{st['tokens_per_verify']:.2f} tok/verify-row, "
              f"{st['pages_dropped']} pages rolled back)")


def quantization_report() -> None:
    """Quantized-serving status of every live ServingEngine: weight mode,
    byte shift, and the PER-LAYER reconstruction-error table from load
    time (``inference/quant.py``) — so a bad checkpoint or scale bug is
    named here at startup instead of debugged from logits. Per-process
    like the program table: call from inside a serving process (or a
    test)."""
    from deepspeed_tpu.inference.serving import live_serving_engines

    engines = [srv for srv in live_serving_engines()
               if srv.quant_status()["enabled"]]
    if not engines:
        return  # nothing to report; stay silent like the program table
    for srv in engines:
        st = srv.quant_status()
        coll = "int8 collectives" if st["collectives"] else "fp collectives"
        if not st["weights"]:
            print(f"quantization: weights fp, {coll} "
                  f"(mp={st['mp_size']})")
            continue
        print(f"quantization: weights {st['weights']} "
              f"({st.get('leaves', 0)} kernels, "
              f"{st.get('quant_weight_bytes', 0)} B = "
              f"{st.get('bytes_ratio', 0):.2f}x of bf16), {coll} "
              f"(mp={st['mp_size']})")
        report = getattr(srv.engine, "quant_report", None) or []
        if report:
            print(f"{'quantized kernel':<48}{'group':>6}{'bytes':>10}"
                  f"{'max_abs_err':>13}{'rel_err':>10}")
            for row in report:
                print(f"{row['param']:<48}{row['group']:>6}"
                      f"{row['quant_bytes']:>10}"
                      f"{row['max_abs_err']:>13.4e}"
                      f"{row['rel_err']:>10.4e}")


def kv_tier_report() -> None:
    """Tiered-KV status of every live ServingEngine in this process: one
    row per tier (capacity, occupancy, demote/promote counters) plus the
    host hit rate and promotion latency percentiles. Per-process like
    the program table: call from inside a serving process (or a test)."""
    from deepspeed_tpu.inference.serving import live_serving_engines

    engines = [srv for srv in live_serving_engines()
               if srv.host_tier is not None]
    if not engines:
        return  # nothing to report; stay silent like the program table
    for srv in engines:
        st = srv.tier_status()
        print(f"{'kv tier':<10}{'capacity':>10}{'blocks':>9}{'bytes':>13}"
              f"{'demoted':>9}{'promoted':>9}{'evicted':>9}")
        for row in st["tiers"]:
            cap = row.get("capacity_blocks")
            print(f"{row['tier']:<10}{str(cap if cap else '-'):>10}"
                  f"{row['blocks']:>9}"
                  f"{str(row.get('bytes', '-')):>13}"
                  f"{row.get('demotions', '-'):>9}"
                  f"{row.get('promotions', '-'):>9}"
                  f"{row.get('evictions', '-'):>9}")
        p50, p95 = st["promote_wait_p50_s"], st["promote_wait_p95_s"]
        print(f"host tier: hit rate {st['host_hit_rate']:.2f} "
              f"({st['host_hits']} hits / {st['host_misses']} misses, "
              f"{st['host_hit_tokens']} tokens), "
              f"{st['pages_promoted']} promoted "
              f"({st['promote_cancelled']} cancelled, "
              f"{st['promote_queue_depth']} in flight), promote wait "
              f"p50 {'n/a' if p50 is None else f'{p50 * 1e3:.1f}ms'} / "
              f"p95 {'n/a' if p95 is None else f'{p95 * 1e3:.1f}ms'}")


def journal_report() -> None:
    """Crash-safety status of every live request journal in this
    process (``inference/serving/journal.py``): directory, segment
    count/bytes, live (non-terminal) records, compaction recency.
    Per-process like the engine and router registries: a fresh
    ``ds_report`` CLI run has no journals; call from inside a serving
    process (or a test) to see them."""
    from deepspeed_tpu.inference.serving import live_request_journals

    journals = live_request_journals()
    if not journals:
        return  # nothing to report; stay silent like the program table
    for j in journals:
        st = j.status()
        age = st["last_compaction_age_s"]
        print(f"request journal: {st['dir']} — {st['segments']} "
              f"segment(s) / {st['bytes']} bytes, "
              f"{st['non_terminal']} non-terminal of "
              f"{st['requests_tracked']} tracked, "
              f"{st['records_appended']} appended "
              f"({st['records_compacted']} compacted, "
              f"{st['torn_tails_truncated']} torn tail(s) truncated), "
              f"last compaction "
              f"{'never' if age is None else f'{age:.0f}s ago'}")


def fleet_report() -> None:
    """Fleet status of every live ServingRouter in this process: the
    per-replica health/goodput table plus routed/requeued/incident
    counters (``monitor/export.py:fleet_statusz`` — the same text the
    fleet /statusz endpoint serves). Per-process like the engine and
    admin-server registries: a fresh ``ds_report`` CLI run has no
    routers; call from inside a serving process (or a test)."""
    from deepspeed_tpu.inference.serving import live_serving_routers
    from deepspeed_tpu.monitor.export import fleet_statusz

    routers = live_serving_routers()
    if not routers:
        return  # nothing to report; stay silent like the program table
    for router in routers:
        print(fleet_statusz(router), end="")


def checkpoint_report(ckpt_dir: str) -> int:
    """Checkpoint fsck (``ds_report --verify-checkpoint DIR``): validate
    every save's manifest in a checkpoint dir, print the last-good tag.
    Exit code 0 iff the ``latest`` pointer resolves to a verified save."""
    from deepspeed_tpu.checkpoint.manifest import fsck

    report = fsck(ckpt_dir)
    print("-" * 60)
    print(f"checkpoint fsck: {ckpt_dir}")
    print("-" * 60)
    if not report["saves"]:
        print("no saves found")
        return 1
    badge = {"verified": GREEN_OK, "legacy": "[LEGACY]", "bad": RED_NO}
    for rec in report["saves"]:
        print(f"{rec['tag']:<32}{badge.get(rec['status'], rec['status']):<20}"
              f"{rec['detail']}")
    print("-" * 60)
    print(f"latest tag: {report['latest']} "
          f"({report['latest_status'] or 'missing'})")
    print(f"last verified (resume target on fallback): {report['last_good']}")
    healthy = report["latest_status"] in ("verified", "legacy")
    heartbeat_report(ckpt_dir)
    return 0 if healthy else 1


def heartbeat_report(ckpt_dir: str) -> None:
    import time

    from deepspeed_tpu.elasticity.heartbeat import read_heartbeats

    beats = read_heartbeats(ckpt_dir)
    if not beats:
        return
    now = time.time()
    print("-" * 60)
    for rank, rec in sorted(beats.items()):
        age = now - max(rec.get("mtime", 0.0), rec.get("time", 0.0))
        note = ""
        if age > 600:
            # not necessarily a wedge: shrunk/finished incarnations leave
            # their last beats behind (the watchdog itself only judges
            # beats from the live incarnation)
            note = "  [stale — rank inactive or from a previous incarnation]"
        print(f"heartbeat rank {rank}: step {rec.get('step')}, "
              f"{age:.0f}s ago (pid {rec.get('pid')}){note}")


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="DeepSpeed-TPU environment / "
                                             "checkpoint health report")
    ap.add_argument("--verify-checkpoint", metavar="DIR", default=None,
                    help="fsck mode: validate every checkpoint manifest in "
                         "DIR and print the last-good tag (exit 1 when the "
                         "latest save fails verification)")
    args = ap.parse_args(argv)
    if args.verify_checkpoint:
        return checkpoint_report(args.verify_checkpoint)
    print("=" * 60)
    print("DeepSpeed-TPU environment report (ds_report)")
    print("=" * 60)
    env_info()
    fault_report()
    trace_report()
    admin_report()
    dslint_report()
    perf_report()
    speculation_report()
    quantization_report()
    kv_tier_report()
    journal_report()
    fleet_report()
    comm_report()
    op_report()
    return 0


def cli_main():
    main()


if __name__ == "__main__":
    raise SystemExit(main())
