"""A serving cell: ``init_inference`` -> ``ServingEngine`` under an open loop
of multi-turn sessions at the cell's fixed rate, driven from one thread.

Set-up: weights from the seed, the correctness probes (which also compile
the one resident mixed step), a lead-in of the same arrival process. Window:
``--seconds`` of the same process, closed at a step boundary; with
``--trace 1`` its last seconds run under the profiler and every number
comes from the part before them. Every time is taken on this file's clock;
the engine's own ``ttft_s`` is timed from submit and is not read.
"""

import heapq
import time

import numpy as np

from benchmark import common, stats as st, trace_reduce
from benchmark.traffic import generator

#: deliberately wrong computations the check must refuse (``--control``)
CONTROLS = ("wrong_window", "int8_kv", "int8_weights", "page_dropped")

COUNTERS = ("steps", "tokens_generated", "prefill_tokens",
            "prefill_tokens_computed", "cached_prefill_tokens",
            "prefix_hits", "preemptions", "requests_submitted",
            "requests_completed", "requests_failed", "requests_timeout",
            "requests_shed", "requests_rejected", "watchdog_trips")


def build(ctx, sizes, control=None):
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    wl = ctx["workload"]
    over = dict(wl["model"])
    if control == "wrong_window":
        over["sliding_window"] = 16      # the published window is 4096
    cfg, model = common.build_model(ctx["config"], sizes, **over)
    mesh = common.cell_mesh(ctx["cell"]["chips"])
    params = common.seeded_bf16_params(model, ctx["seed"])
    extra = {"int8_kv": {"kv_cache_int8": True},
             "int8_weights": {"quantize_weights": "int8"}}.get(control, {})
    engine = ds.init_inference(model, params=params, dtype=wl["dtype"],
                               mesh=mesh, **extra)
    return model, params, ServingEngine(engine, ServingConfig(**wl["serving"]))


def counters(srv):
    out = {k: getattr(srv.metrics, k) for k in COUNTERS}
    out["compiles"] = sum(srv.compile_counts.values())
    return out


# -- correctness: seeded probes through the real path, against the reference -

def paged_last_logits(module, params, prompt, block_size, kv_dtype,
                      drop_page=None):
    """Last-position logits of one prompt prefilled as a single ragged row
    through the model's paged mixed-step branch (the kernel the engine's
    step runs). ``drop_page`` points one block-table entry at an empty page:
    the negative control."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.layers import paged_cache_index

    n = len(prompt)
    nb = -(-n // block_size)
    pool = module.init_paged_cache(nb + 1, block_size, dtype=kv_dtype)
    idx = paged_cache_index(
        np.arange(nb, dtype=np.int32)[None], np.arange(n)[None], [n],
        chunk_start=[0], token_rows=np.zeros((1, n), np.int32),
        query_start=[0], query_len=[n])

    @jax.jit
    def fill(params, pool, ids, idx):
        logits, pool = module.apply({"params": params}, ids, cache=pool,
                                    cache_index=idx)
        return logits[0, -1].astype(jnp.float32), pool

    ids = jnp.asarray([prompt], jnp.int32)
    logits, pool = fill(params, pool, ids, idx)
    if drop_page is None:
        return np.asarray(logits)
    # second pass, one token: re-read the filled pool with a page missing
    tables = np.arange(nb, dtype=np.int32)[None].copy()
    tables[0, drop_page] = nb
    idx1 = paged_cache_index(
        tables, np.asarray([[n - 1]]), [n], chunk_start=[n - 1],
        token_rows=np.zeros((1, 1), np.int32), query_start=[0],
        query_len=[1])
    logits, _ = fill(params, pool, ids[:, -1:], idx1)
    return np.asarray(logits)


def probe_prompts(ctx, sizes):
    """The probes' prompts from the seed: lengths spread over the mix, one
    over several prefill chunks, two behind one shared system prompt."""
    mix, V = ctx["mix"], sizes["vocab_size"]
    out = []
    for i, p in enumerate(ctx["workload"]["check"]["probes"]):
        system = [] if p["system"] is None else generator.token_ids(
            ctx["seed"], p["system"], mix["system_prompts"]["tokens"][
                p["system"]], V)
        out.append(system + generator.token_ids(ctx["seed"], 100 + i,
                                                p["user_tokens"], V))
    return out


def check(ctx, model, params, srv, sizes, control=None):
    """Logits, not tokens. (1) Every token the engine's real path chose for
    a probe must lie within the tolerance of the reference's own maximum at
    that position, in units of the reference logits' spread: blind to ties,
    not to a wrong mask or a dropped page. (2) The paged branch's logits for
    the longest probe against the reference's, as a relative L2. (3) The
    K/V pool and the weights are stored in the types the cell's file
    states."""
    import jax
    import jax.numpy as jnp

    ref = common.load_file_module("reference", ctx["config"]["reference"])
    chk = ctx["workload"]["check"]
    prompts = probe_prompts(ctx, sizes)
    new = [p["new_tokens"] for p in chk["probes"]]
    rids = [srv.submit(prompts[0], max_new_tokens=new[0])]
    srv.run()       # pages index as chunks land: the shared prefix first
    rids += [srv.submit(p, max_new_tokens=n)
             for p, n in zip(prompts[1:], new[1:])]
    srv.run()
    outs = [srv.poll(r) for r in rids]
    pad = max(len(p) + n for p, n in zip(prompts, new))
    margins = []
    for prompt, out in zip(prompts, outs):
        toks = list(out.tokens)
        if not toks:
            margins.append(float("inf"))
            continue
        seq = prompt + toks[:-1]
        ids = jnp.asarray(seq + [0] * (pad - len(seq)), jnp.int32)
        hidden = ref.hidden_states(params, sizes, ids)
        rows = np.asarray(ref.logits(
            params, hidden[len(prompt) - 1:len(seq)]))
        chosen = rows[np.arange(len(toks)), toks]
        margins += list((rows.max(-1) - chosen) / rows.std(-1))
    longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    ids = jnp.asarray(prompts[longest], jnp.int32)
    ref_last = np.asarray(ref.logits(
        params, ref.hidden_states(params, sizes, ids)[-1:]))[0]
    # the engine's own module, weights and cache type: a quantized engine
    # shows here
    got_last = paged_last_logits(
        srv.engine.module, srv.engine.params, prompts[longest],
        srv.config.block_size, srv.pool["k"].dtype,
        drop_page=1 if control == "page_dropped" else None)
    stats = {"margin_max": float(max(margins)),
             "margin_mean": float(np.mean(margins)),
             "paged_rel_l2": common.rel_l2(got_last, ref_last),
             "probe_tokens": len(margins),
             "probe_states": [o.state for o in outs]}
    # what the cell guarantees to store: 8-bit K/V or weights err no more
    # than bf16's own rounding does, so no tolerance on logits refuses them
    stored = {str(x.dtype) for x in jax.tree_util.tree_leaves(
        srv.engine.params)}
    stats.update(kv_dtype=str(srv.pool["k"].dtype),
                 weight_dtypes=sorted(stored))
    verdicts = {
        "margin_max": stats["margin_max"] <= chk["margin_tol"],
        "paged_rel_l2": stats["paged_rel_l2"] <= chk["paged_rel_l2_tol"],
        "finite": bool(np.isfinite(got_last).all()
                       and np.isfinite(margins).all()),
        "stored_as_stated": stats["kv_dtype"] == chk["kv_dtype"]
        and stored == {chk["weight_dtype"]},
    }
    return all(verdicts.values()), {**stats, "verdicts": verdicts}


# -- the open loop -----------------------------------------------------------

class Loop:
    """Sessions in, timelines out. One thread: submit what is due, step,
    stamp the tokens each live request gained, schedule next turns."""

    def __init__(self, ctx, srv, sizes, sessions):
        self.ctx, self.srv, self.V = ctx, srv, sizes["vocab_size"]
        self.mix = ctx["mix"]
        self.t0 = None
        self.due = []            # heap of (due_s, order, session, turn no.)
        self.live = {}           # rid -> record
        self.records = []
        self.step_ms = []        # (end_s, duration ms)
        self.queue_depth = []    # (time_s, waiting requests)
        self.active = []         # (time_s, requests in a slot)
        self.system = [generator.token_ids(ctx["seed"], i, n, self.V)
                       for i, n in enumerate(
                           self.mix["system_prompts"]["tokens"])]
        for k, s in enumerate(sessions):
            s["history"] = list(self.system[s["system"]])
            s["id"] = k
            heapq.heappush(self.due, (s["arrival_s"], k, k, 0))
        self.sessions = sessions
        self.order = len(sessions)

    def now(self):
        return time.perf_counter() - self.t0

    def submit_due(self):
        now = self.now()
        while self.due and self.due[0][0] <= now:
            due, _, k, j = heapq.heappop(self.due)
            s = self.sessions[k]
            turn = s["turns"][j]
            prompt = s["history"] + generator.token_ids(
                self.ctx["seed"], 1000 + 16 * k + j, turn["user_tokens"],
                self.V)
            rec = {"due": due, "submit": self.now(), "token_times": [],
                   "state": "queued", "session": k, "turn": j,
                   "prompt": prompt}
            self.records.append(rec)
            with trace_reduce.annotate("bench.submit"):
                rid = self.srv.try_submit(
                    prompt, max_new_tokens=turn["answer_tokens"])
            if rid is None:
                rec["state"] = "rejected"
            else:
                self.live[rid] = rec

    def harvest(self):
        now = self.now()
        for rid in list(self.live):
            rec, req = self.live[rid], self.srv.request(rid)
            rec["token_times"] += [now] * (len(req.tokens)
                                           - len(rec["token_times"]))
            rec["state"] = req.state.value
            if not req.done:
                continue
            del self.live[rid]
            s = self.sessions[rec["session"]]
            nxt = rec["turn"] + 1
            if rec["state"] == "finished" and nxt < len(s["turns"]):
                s["history"] = rec["prompt"] + list(req.tokens)
                self.order += 1
                heapq.heappush(self.due, (now + s["turns"][nxt]["think_s"],
                                          self.order, rec["session"], nxt))
            rec.pop("prompt")
            self.srv.forget(rid)

    def run_until(self, end_s):
        """Drive until the first step boundary at or after ``end_s``."""
        srv = self.srv
        while True:
            self.submit_due()
            if srv.has_work():
                t = time.perf_counter()
                with trace_reduce.annotate("bench.srv_step"):
                    srv.step()
                dt = time.perf_counter() - t
                with trace_reduce.annotate("bench.harvest"):
                    self.harvest()
                now = self.now()
                self.step_ms.append((now, 1e3 * dt))
                self.queue_depth.append((now, srv.sched.queue_depth))
                self.active.append((now, len(self.live)
                                    - srv.sched.queue_depth))
            else:
                nxt = self.due[0][0] if self.due else end_s
                with trace_reduce.annotate("bench.no_request_due"):
                    time.sleep(max(0.0, min(nxt, end_s) - self.now()))
            if self.now() >= end_s:
                return self.now()


def run(ctx):
    sizes = ctx["sizes"]
    wl = ctx["workload"]
    model, params, srv = build(ctx, sizes, ctx.get("control"))
    ctx["emit"]({"phase": "engine", "s": time.perf_counter() - ctx["t_start"]})
    correct, stats = check(ctx, model, params, srv, sizes, ctx.get("control"))
    ctx["emit"]({"phase": "check", "correct": correct, **stats,
                 "compile_counts": dict(srv.compile_counts)})
    if ctx.get("check_only"):
        return {"correct": correct, "stats": stats}

    # A traced run clocks the window less its last ``trace_seconds`` and
    # runs those under the profiler (train.py does the same): starting the
    # profiler stalls this loop for a second or two.
    tail_s = ctx["trace_seconds"] if ctx["trace"] else 0
    lead_in = wl["lead_in_s"]
    loop = Loop(ctx, srv, sizes, generator.sessions(
        ctx["mix"], wl["sessions_per_s"], lead_in + ctx["seconds"],
        ctx["seed"]))
    loop.t0 = time.perf_counter()
    w0 = loop.run_until(lead_in)
    before = counters(srv)
    setup_s = time.perf_counter() - ctx["t_start"]
    w1 = loop.run_until(w0 + ctx["seconds"] - tail_s)
    after = counters(srv)
    delta = {k: after[k] - before[k] for k in after}
    if delta["compiles"]:
        ctx["emit"]({"defect": "compile inside the window",
                     "programs": dict(srv.compile_counts)})
    win = st.serve_window(loop.records, w0, w1, wl["grace_s"])
    trace = None
    if tail_s:
        with trace_reduce.traced(ctx["trace_dir"]):
            loop.run_until(w1 + tail_s)
        trace = trace_reduce.reduce_dir(ctx["trace_dir"])
    in_window = lambda series: [v for t, v in series if w0 < t <= w1]
    depth = in_window(loop.queue_depth)
    ctx["emit"]({"phase": "window", "w0": w0, "w1": w1, "counters": delta,
                 "ttft_samples": len(win["ttft_s"]),
                 "gap_samples": len(win["gaps_s"]),
                 "lag_p95_ms": 1e3 * (st.percentile(win["lag_s"], 95) or 0),
                 "queue_depth_third": depth[len(depth) // 3] if depth else 0,
                 "queue_depth_end": depth[-1] if depth else 0,
                 "queue_depth_max": max(depth, default=0),
                 "active_by_10s": [
                     round(float(np.mean([v for t, v in loop.active
                                          if a <= t < a + 10] or [0])), 2)
                     for a in range(0, int(w1), 10)],
                 "states": {s: sum(1 for r in loop.records
                                   if r["state"] == s)
                            for s in {r["state"] for r in loop.records}}})
    ms = lambda x: None if x is None else 1e3 * x
    return {
        "correct": correct,
        "attempted": win["attempted"],
        "failed": win["failed"],
        "end_to_end": {
            "setup_s": setup_s,
            "ttft_p95_ms": ms(st.percentile(win["ttft_s"], 95)),
            "tpot_p95_ms": ms(st.percentile(win["gaps_s"], 95)),
            "serve_tokens_per_s": win["tokens"] / win["seconds"],
        },
        "observed": {"kind": "serve", "counters": delta,
                     "step_ms": in_window(loop.step_ms),
                     "ttft_s": win["ttft_s"], "gaps_s": win["gaps_s"],
                     "lag_s": win["lag_s"], "window_s": win["seconds"],
                     "queue_depth": depth},
        "trace": trace,
    }
