"""What each named kernel NEEDS for one call, from its shapes: operations
and bytes, and from those the least time the chip could take. A kernel's
roofline share is that least time over the time the trace shows.

Recomputed work does not count, padding does not count, and a grouped-query
kernel is charged the key/value heads it needs to read, not the copies the
caller may have made. Peaks come from ``benchmark/peaks.json``.
"""

import json
import sys

from benchmark import common, flops, scope_reduce
from benchmark.traffic import generator

#: bytes of one element as the configurations' ``dtype`` strings name it
DTYPE_BYTES = {"bf16": 2, "bfloat16": 2, "fp32": 4, "float32": 4, "int8": 1}


def cell_name(run):
    """The cell a reader is asked about: ``run["cell"]`` where a test says,
    else the ``--workload`` this process was started with (``run.py`` hands
    the readers the run and not its context)."""
    if run.get("cell"):
        return run["cell"]
    argv = sys.argv
    return argv[argv.index("--workload") + 1] if "--workload" in argv \
        else None


def cell_files(run):
    """(sizes as run, workload, traffic mix) of the run's cell, or None."""
    name = cell_name(run)
    bench = common.load_benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        return None
    workload = common.load_json("workloads", f"{name}.json")
    config = common.load_json("configs", f"{cell['config']}.json")
    return (common.sizes_of(config, workload["depth"]), workload,
            generator.load_mix(cell["traffic"]))


def flash_fwd(batch, seq_len, q_heads, kv_heads, head_dim, window=None,
              elem=2):
    """Causal (windowed) flash forward: scores and values are 2 x 2 x D
    operations for every (query, attended key) pair of every query head;
    q and o move once per query head, k and v once per key/value head,
    the log-sum-exp row in float32."""
    pairs = batch * q_heads * seq_len * flops.mean_attended_keys(
        seq_len, window)
    moved = elem * batch * seq_len * head_dim * (2 * q_heads + 2 * kv_heads)
    return {"flops": 4 * head_dim * pairs,
            "bytes": moved + 4 * batch * q_heads * seq_len}


def flash_bwd(batch, seq_len, q_heads, kv_heads, head_dim, window=None,
              elem=2):
    """Flash backward (dq and dkv kernels together): the scores again,
    dP, dV, dQ and dK: five matrix products to the forward's two. Reads q,
    k, v, o's cotangent and the two float32 rows; writes dq, dk, dv."""
    fwd = flash_fwd(batch, seq_len, q_heads, kv_heads, head_dim, window,
                    elem)
    moved = elem * batch * seq_len * head_dim * (3 * q_heads + 4 * kv_heads)
    return {"flops": 2.5 * fwd["flops"],
            "bytes": moved + 2 * 4 * batch * q_heads * seq_len}


def ragged_paged_attention(query_tokens, rows, context_tokens, q_heads,
                           kv_heads, head_dim, kv_elem=2, elem=2):
    """One layer's ragged paged attention over a packed step: every row
    reads its context's keys and values once (``context_tokens`` is the sum
    of the rows' context lengths), and each of a row's queries meets about
    its whole context (a decode row exactly; a chunk of c tokens c/2 fewer,
    which is left out)."""
    per_row = query_tokens / max(rows, 1)
    return {"flops": 4 * head_dim * q_heads * per_row * context_tokens,
            "bytes": 2 * kv_elem * kv_heads * head_dim * context_tokens
            + 2 * elem * q_heads * head_dim * query_tokens}


def least_seconds(cost, device_kind):
    """(least time of one call on this chip, which peak bounds it)."""
    peaks = common.load_json("peaks.json")[device_kind]
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes,
                                                              "bytes")


def roofline_share(run, reduced, kernels, cost):
    """100 x least time / measured time per call, over the calls of
    ``kernels`` (summed per call: the backward's two kernels make one
    call), with the bound on an observation line. None off the chip, or
    where the trace has none of the kernels."""
    if run["device"]["platform"] != "tpu":
        return None        # no peak to be a share of
    rows = [reduced["by_kernel"].get(k) for k in kernels]
    if not all(rows) or not all(r["calls"] for r in rows):
        return None
    per_call = sum(r["s"] / r["calls"] for r in rows)
    least, bound = least_seconds(cost, run["device"]["kind"])
    print(json.dumps({"observation": "kernel_roofline",
                      "kernels": list(kernels), "bound": bound,
                      "least_ms": 1e3 * least, "measured_ms": 1e3 * per_call,
                      "flops": cost["flops"], "bytes": cost["bytes"]}),
          flush=True)
    return 100.0 * least / per_call


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a training cell against their rooflines."""
    if run["observed"]["kind"] != "train":
        return None
    reduced = scope_reduce.reduced(run)
    files = cell_files(run)
    if not reduced or not files:
        return None
    sizes, _, mix = files
    cost = cost_fn(mix["sequences_per_chip"], mix["seq_len"],
                   sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"],
                   sizes.get("sliding_window"))
    return roofline_share(run, reduced, kernels, cost)
