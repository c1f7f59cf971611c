"""Shared by the benchmark's tests: a cell's context at its tiny size."""

from benchmark import common
from benchmark import run as bench_run


def with_cell(bench, cell):
    """``bench`` with the cell entered from its workload file, as the PR
    that adds it would: the serving cell is kept as files only (PERF.md
    section 7)."""
    if cell not in [w["name"] for w in bench["workloads"]]:
        wl = common.load_json("workloads", f"{cell}.json")
        bench["workloads"].append(
            {"name": cell, "config": wl["config"], "traffic": wl["traffic"],
             "chips": wl["chips"], "why": "entered by the tests"})
    return bench


def tiny_context(cell, seed, control=None):
    ctx = bench_run.context(with_cell(common.load_benchmark(), cell), cell,
                            seed, tiny=True)
    ctx["emit"] = lambda obj: None
    ctx["control"] = control
    return ctx, common.load_file_module("kinds", ctx["workload"]["kind"])


def train_check(cell, seed, control=None):
    ctx, kind = tiny_context(cell, seed, control)
    engine = kind.build_engine(ctx, ctx["sizes"], control)
    return kind.check(ctx, engine, ctx["sizes"])


def serve_check(cell, seed, control=None):
    ctx, kind = tiny_context(cell, seed, control)
    model, params, srv = kind.build(ctx, ctx["sizes"], control)
    return kind.check(ctx, model, params, srv, ctx["sizes"], control)
