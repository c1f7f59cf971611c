"""Model-based MFU tuner: coordinate descent over the performance levers.

Counterpart of the reference's guided search
(``deepspeed/autotuning/tuner/model_based_tuner.py:1`` +
``tuner/cost_model.py:1``): the reference generates candidate ds_configs
from templates, fits an XGBoost cost model on measured runs, and evaluates
predicted-best-first with early stopping. TPU-native shape: the levers that
move MFU here are *compilation* knobs — remat policy, flash-attention tile
sizes, chunked-loss size, micro-batch x gradient-accumulation split,
Pallas-vs-XLA kernels — so candidates rebuild the model config
(``dataclasses.replace``) and re-jit in-process instead of forking cluster
jobs. The search is a memoized coordinate descent, with the ridge cost
model supplying the predicted-best-first evaluation order and pruning
within each axis. No cell runs it (ROADMAP D1b).

Every evaluation is memoized (and persisted to ``results_dir``) so repeated
calls — or a resumed tuning session — never re-measure a spec.
"""

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.logging import log_dist, logger

#: The full lever space (reference core space analog). ``bg`` is
#: (micro_batch, gas).
LEVER_AXES: Dict[str, List[Any]] = {
    "bg": [(8, 8), (16, 4), (16, 8), (32, 4), (8, 16)],
    "fq": [256, 512, 1024],
    "fk": [256, 512, 1024],
    "lchunk": [0, 1024, 2048, 4096],
    "policy": ["dots", "nothing", "offload_dots_no_batch"],
    "padam": [False, True],
    "attn": ["flash", "xla"],
}

_DEFAULT_SPEC = {"bg": (8, 8), "fq": 512, "fk": 512, "lchunk": 2048,
                 "policy": "dots", "padam": False, "attn": "flash"}

_POLICY_ORDER = ["nothing", "dots", "dots_no_batch", "offload_dots_no_batch"]
_ATTN_ORDER = ["xla", "flash"]


def spec_key(spec: Dict[str, Any]) -> str:
    b, g = spec["bg"]
    return (f"b{b}g{g},{spec['policy']},{spec['attn']},fq{spec['fq']}"
            f"k{spec['fk']},lc{spec['lchunk']},padam{int(spec['padam'])}")


def spec_features(spec: Dict[str, Any]) -> List[float]:
    """Numeric embedding for the cost model (categoricals -> ordinals, the
    reference flattens configs the same way before fitting)."""
    b, g = spec["bg"]
    return [float(b), float(g), float(b * g), float(spec["fq"]),
            float(spec["fk"]), float(spec["lchunk"]),
            float(_POLICY_ORDER.index(spec["policy"])
                  if spec["policy"] in _POLICY_ORDER else len(_POLICY_ORDER)),
            float(_ATTN_ORDER.index(spec["attn"])
                  if spec["attn"] in _ATTN_ORDER else len(_ATTN_ORDER)),
            float(bool(spec["padam"]))]


class MFUTuner:
    """Coordinate descent with cost-model-guided in-axis ordering/pruning.

    ``model_config`` must be one of this framework's model-config
    dataclasses (Llama family etc.) — the levers map onto its fields
    (``remat_policy``, ``flash_block_q/k``, ``loss_chunk``,
    ``attention_impl``); ``model_cls(model_config)`` rebuilds the model.
    ``make_batch(global_batch_size)`` supplies a training batch dict.
    """

    def __init__(self, model_cls, model_config, base_config: Dict,
                 make_batch: Callable[[int], Dict],
                 axes: Optional[Dict[str, Sequence]] = None,
                 mesh=None, steps: int = 3, warmup: int = 1,
                 results_dir: Optional[str] = None,
                 measure_fn: Optional[Callable[[Dict], float]] = None,
                 prune_after: int = 6):
        self.model_cls = model_cls
        self.model_config = model_config
        self.base_config = base_config
        self.make_batch = make_batch
        # partial override keeps defaults for unspecified axes (an axis can
        # be pinned by passing a single-value list)
        self.axes = {k: list(v) for k, v in {**LEVER_AXES,
                                             **(axes or {})}.items()}
        self.mesh = mesh
        self.steps = steps
        self.warmup = warmup
        self.results_dir = results_dir
        self.measure_fn = measure_fn
        #: minimum measurements before the cost model orders/prunes an axis
        self.prune_after = prune_after
        self.results: Dict[str, Dict[str, Any]] = {}
        self.evaluations = 0  # actual measurements (memo hits excluded)
        self.pruned = 0
        if results_dir:
            os.makedirs(results_dir, exist_ok=True)
            memo = os.path.join(results_dir, "mfu_results.json")
            if os.path.exists(memo):
                with open(memo) as f:
                    self.results = json.load(f)

    # -- evaluation ------------------------------------------------------

    def _engine_config(self, spec: Dict) -> Tuple[Any, Dict]:
        micro, gas = spec["bg"]
        mcfg = dataclasses.replace(
            self.model_config, remat=True, remat_policy=spec["policy"],
            attention_impl=spec["attn"], flash_block_q=spec["fq"],
            flash_block_k=spec["fk"], loss_chunk=spec["lchunk"])
        opt = dict(self.base_config.get("optimizer", {"type": "AdamW"}))
        opt_params = dict(opt.get("params", {}))
        if spec["padam"]:
            opt_params["pallas"] = True
        else:
            opt_params.pop("pallas", None)
        opt["params"] = opt_params
        dcfg = {**self.base_config, "optimizer": opt,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": gas}
        dcfg.pop("train_batch_size", None)  # derived: micro x gas x dp
        return mcfg, dcfg

    def _measure(self, spec: Dict) -> Dict[str, Any]:
        """tokens/sec for one spec (higher is better); memoized."""
        k = spec_key(spec)
        if k in self.results:
            return self.results[k]
        rec: Dict[str, Any] = {"spec": {**spec, "bg": list(spec["bg"])}}
        self.evaluations += 1
        try:
            if self.measure_fn is not None:  # test seam / remote backend
                rec["tokens_per_sec"] = float(self.measure_fn(spec))
            else:
                rec["tokens_per_sec"] = self._measure_inprocess(spec)
        except Exception as e:  # invalid combo / OOM: a real result (final)
            rec["error"] = f"{type(e).__name__}: {e}"
            logger.debug("mfu_tuner candidate failed", exc_info=True)
        self.results[k] = rec
        if self.results_dir:
            with open(os.path.join(self.results_dir, "mfu_results.json"),
                      "w") as f:
                json.dump(self.results, f, indent=1)
        log_dist(f"mfu_tuner {k}: "
                 f"{rec.get('tokens_per_sec', rec.get('error'))}", ranks=[0])
        return rec

    def _measure_inprocess(self, spec: Dict) -> float:
        import deepspeed_tpu as ds
        from ..parallel import topology
        from .autotuner import timed_step_seconds

        mcfg, dcfg = self._engine_config(spec)
        topology.set_mesh(None, None)
        model = self.model_cls(mcfg)
        probe = self.make_batch(1)
        engine, *_ = ds.initialize(
            model=model, config=dcfg, mesh=self.mesh,
            example_batch={kk: v[:1] for kk, v in probe.items()})
        batch = self.make_batch(engine.train_batch_size)
        seq = next(iter(batch.values())).shape[1]
        dt = timed_step_seconds(engine, batch, self.steps, self.warmup)
        return engine.train_batch_size * seq / dt

    # -- search ----------------------------------------------------------

    def _measured(self) -> List[Tuple[List[float], float]]:
        """(features, tokens/sec) for every SUCCESSFUL measurement —
        errored records never feed (or gate) the cost model."""
        return [(spec_features(r["spec"]), r["tokens_per_sec"])
                for r in self.results.values() if "tokens_per_sec" in r]

    def _axis_order(self, axis: str, cur_spec: Dict, values: List) -> List:
        """Current value first; the rest predicted-best-first once the cost
        model has enough measurements (reference
        ``find_estimated_top_configs``)."""
        from .cost_model import rank_by_cost_model

        rest = [v for v in values if v != cur_spec[axis]]
        ranked = rank_by_cost_model(
            self._measured(),
            [spec_features({**cur_spec, axis: v}) for v in rest],
            min_measured=self.prune_after)
        if ranked is not None:
            rest = [rest[i] for i in ranked]
        return [cur_spec[axis]] + rest

    def tune(self, budget_evals: int = 64,
             start: Optional[Dict] = None) -> Dict[str, Any]:
        """Run the descent; returns ``{"spec", "tokens_per_sec",
        "model_config", "config", "evaluations", "pruned"}`` for the best
        measured point. Cycles axes until no axis improves or the budget is
        spent; within an axis, candidates are tried predicted-best-first and
        the axis is abandoned after ``axis_patience`` consecutive
        non-improvements (the model-based tuner's early stopping, applied
        per line search)."""
        cur = dict(start or {k: (self.axes[k][0] if k not in _DEFAULT_SPEC
                                 or _DEFAULT_SPEC[k] not in self.axes[k]
                                 else _DEFAULT_SPEC[k]) for k in self.axes})
        axis_patience = 2
        # resume: restart the descent FROM the best persisted measurement —
        # both the acceptance threshold (best_rec) and the walk position
        # (cur). Without this a resumed tune starts at the default spec with
        # a warm cost model, can terminate without revisiting the previously
        # best spec, and overwrites best_mfu.json with a WORSE best.
        best_rec = None
        for rec in self.results.values():
            if rec.get("tokens_per_sec") is not None and (
                    best_rec is None
                    or rec["tokens_per_sec"] > best_rec["tokens_per_sec"]):
                best_rec = rec
        if best_rec is not None and start is None:
            resumed = {**best_rec["spec"],
                       "bg": tuple(best_rec["spec"]["bg"])}
            if set(resumed) == set(self.axes):
                cur = resumed
        improved = True
        while improved and self.evaluations < budget_evals:
            improved = False
            for axis, values in self.axes.items():
                stale = 0
                # guided iff the tail below was cost-model ordered HERE —
                # the prune decision must match the ordering decision
                guided = len(self._measured()) >= self.prune_after
                for v in self._axis_order(axis, cur, values):
                    if self.evaluations >= budget_evals:
                        break
                    trial = {**cur, axis: v}
                    known = spec_key(trial) in self.results
                    rec = self._measure(trial)
                    t = rec.get("tokens_per_sec")
                    if t is not None and (
                            best_rec is None
                            or t > best_rec["tokens_per_sec"]):
                        best_rec = rec
                        if cur[axis] != v:
                            improved = True
                        cur = trial
                        stale = 0
                    elif not known:
                        stale += 1
                        if stale >= axis_patience and guided:
                            # cost-model-ordered tail is predicted worse;
                            # abandon the rest of this line search
                            self.pruned += len(
                                [u for u in values if u != v and
                                 spec_key({**cur, axis: u})
                                 not in self.results])
                            break
        if best_rec is None:
            errs = [r.get("error") for r in self.results.values()]
            raise RuntimeError(f"mfu tuning: every candidate failed ({errs})")
        best_spec = {**best_rec["spec"], "bg": tuple(best_rec["spec"]["bg"])}
        mcfg, dcfg = self._engine_config(best_spec)
        out = {"spec": best_spec,
               "tokens_per_sec": best_rec["tokens_per_sec"],
               "model_config": mcfg, "config": dcfg,
               "evaluations": self.evaluations, "pruned": self.pruned}
        if self.results_dir:
            with open(os.path.join(self.results_dir, "best_mfu.json"),
                      "w") as f:
                json.dump({"spec": {**best_spec, "bg": list(best_spec["bg"])},
                           "tokens_per_sec": best_rec["tokens_per_sec"],
                           "config": dcfg, "evaluations": self.evaluations,
                           "pruned": self.pruned}, f, indent=2)
        log_dist(f"mfu_tuner best: {spec_key(best_spec)} "
                 f"({best_rec['tokens_per_sec']:.0f} tok/s, "
                 f"{self.evaluations} evals, {self.pruned} pruned)",
                 ranks=[0])
        return out
