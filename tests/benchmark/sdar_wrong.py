"""Deliberately wrong computations of ``sdar-30b-a3b.train.8k``'s model, each
one thing of the block-diffusion training pass as ISSUE 58 wrote it down left
out or replaced, for the cell's check to refuse: patches of module-level
names of ``deepspeed_tpu/models/sdar.py`` and ``llama.py`` and of the rule
``flash_attention.BlockDiffusion`` itself (every parameter still exists, so
the reference reads the same tree), and the plain reference itself computed
from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). A wrong RULE answers ``sees``
wrongly and derives its tile table from that answer tile by tile, so the
flash kernels and the XLA path compute the same wrong thing. The model's
label-free call is the training pass up to the head, so the logits the check
compares see every one of them but the two wrong weightings of the loss. Used
by the CPU tests at the tiny size and, through ``kinds/train.py check`` at
the published widths, by ``python3 tests/benchmark/sdar_wrong.py`` on the
chip (PERF.md section 6)."""

import contextlib

import numpy as np

import deepspeed_tpu.models.llama as llama
import deepspeed_tpu.models.sdar as sdar
from deepspeed_tpu.ops.pallas.flash_attention import BlockDiffusion
from keye_vl2_wrong import _head_norm_left_out
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)


def _tiles_of_sees(self, r0, r1, c0, c1):
    """A rule's table read off its own ``sees``, tile by tile."""
    r0, r1, c0, c1 = np.broadcast_arrays(r0, r1, c0, c1)
    some, whole = np.zeros(r0.shape, bool), np.zeros(r0.shape, bool)
    for at in np.ndindex(r0.shape):
        seen = np.asarray(self.sees(
            np.arange(r0[at], r1[at] + 1)[:, None],
            np.arange(c0[at], c1[at] + 1)[None, :]))
        some[at], whole[at] = seen.any(), seen.all()
    return some, whole


def _leak(self, rows, cols):
    """A noised block also sees its OWN clean block (``>`` for ``>=``)."""
    (cq, bq), (cj, bj) = self._split(rows), self._split(cols)
    return ((cq == cj) & (bq == bj)) | (cj & (bq >= bj))


def _causal_inside(self, rows, cols):
    """Inside a block a token sees the tokens up to itself alone."""
    (cq, bq), (cj, bj) = self._split(rows), self._split(cols)
    earlier = cols - self.half * cj <= rows - self.half * cq
    return ((cq == cj) & (bq == bj) & earlier) \
        | (cj & (bq + cq > bj) & ((bq != bj) | earlier))


def _positions_run_on(m):
    import jax.numpy as jnp

    return {"doubled_positions": lambda batch, length: jnp.broadcast_to(
        jnp.arange(2 * length)[None, :], (batch, 2 * length))}


#: name -> [(module or class, patches of it ({attribute: replacement}))]
WRONG = {
    "noised_block_sees_its_clean_block": [(BlockDiffusion, lambda m: {
        "sees": _leak, "tiles": _tiles_of_sees})],
    "causal_inside_a_block": [(BlockDiffusion, lambda m: {
        "sees": _causal_inside, "tiles": _tiles_of_sees})],
    "positions_run_on_to_2L": [(sdar, _positions_run_on)],
    "one_over_t_left_out": [(sdar, lambda m: {
        "loss_weights": lambda masked, t: masked / (0 * t + 1)})],
    "loss_over_all_noised_rows": [(sdar, lambda m: {
        "loss_weights": lambda masked, t: 1 / t})],
    "head_norm_left_out": [(llama, _head_norm_left_out)],
}
#: the wrong computations the label-free logits do not see
LOSS_ONLY = ("one_over_t_left_out", "loss_over_all_noised_rows")


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = [(module, k, v) for module, make in WRONG[name]
               for k, v in make(module).items()]
    saved = [(module, k, module.__dict__[k]) for module, k, _ in patches]
    try:
        for module, k, v in patches:
            setattr(module, k, v)
        yield
    finally:
        for module, k, v in saved:
            setattr(module, k, v)


def main(seeds, tiny=False):
    """On the chip, at the cell's own sizes (``--tiny``: here, at the tiny): the sound model, each wrong
    computation and the reference from float8 weights through
    ``kinds/train.py check`` itself -- the comparison that decides
    ``correct`` -- on a fresh engine each, one JSON line a reading. The
    check's repeated steps are cut to two (``falling`` is not what is read
    here): ``PYTHONPATH=. python3 tests/benchmark/sdar_wrong.py <data seed>
    ...`` from the root of a checkout."""
    import json

    from benchmark import common, run as bench_run
    from deepspeed_tpu.utils.jax_compat import configure_compile_cache

    configure_compile_cache()
    bench = common.load_benchmark()
    kind = common.load_file_module("kinds", "train")
    cell = "sdar-30b-a3b.train.8k"
    for seed in seeds:
        for name in ("sound", *WRONG, "reference_fp8_e4m3",
                     "reference_fp8_e5m2"):
            ctx = bench_run.context(bench, cell, seed, tiny=tiny)
            ctx["emit"] = lambda line: None
            ctx["workload"] = {**ctx["workload"], "warmup_steps": 1}
            how = contextlib.nullcontext() if name == "sound" else \
                reference_from_float8(*((4, 3) if name.endswith("e4m3")
                                        else (5, 2))) \
                if name.startswith("reference_fp8") else wrong(name)
            with how:
                ok, stats = kind.check(
                    ctx, kind.build_engine(ctx, ctx["sizes"]), ctx["sizes"])
            print(json.dumps({"seed": seed, "name": name, "correct": ok,
                              **stats}), flush=True)
            bench_run.free_device_memory()


if __name__ == "__main__":
    import sys

    main([int(s) for s in sys.argv[1:] if s != "--tiny"],
         "--tiny" in sys.argv)
