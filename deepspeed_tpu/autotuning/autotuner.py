"""Autotuning: profile the model, generate candidate configs, measure, pick.

Counterpart of ``deepspeed/autotuning/autotuner.py:26`` (``Autotuner``) +
``scheduler.py:27`` (``ResourceManager``) + ``tuner/``: the reference forks
cluster jobs per candidate ds_config and reads back metrics. TPU-native
shape: every candidate is an in-process experiment — build an engine with the
overridden config on the live mesh, time a few steps, tear down — because
jit-compiled programs are isolated by construction (no process isolation
needed to try a different ZeRO stage or micro batch).

Tuned dimensions (the reference's core space): ZeRO stage and micro batch
size per device; ``fast`` mode fixes the stage and sweeps micro batch only.
Results are written one JSON per experiment under ``results_dir`` plus
``best_config.json`` (reference ``autotuning_results/`` layout).
"""

import dataclasses
import json
import os
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..utils.logging import log_dist, logger


@dataclasses.dataclass
class Experiment:
    name: str
    overrides: Dict[str, Any]            # config deltas for this candidate
    metric_value: Optional[float] = None  # higher is better
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.metric_value is not None


def _merged(base: Dict, overrides: Dict) -> Dict:
    out = json.loads(json.dumps(base))  # deep copy via json (configs are json)
    for k, v in overrides.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def timed_step_seconds(engine, batch, steps: int, warmup: int = 0) -> float:
    """Mean seconds per ``train_batch`` after compile + warmup, fenced by
    ``block_until_ready`` on the last loss (each step consumes the state
    the one before it produced)."""
    import jax

    loss = engine.train_batch(batch=batch)  # compile
    for _ in range(warmup):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / steps


class Autotuner:
    """See module docstring. ``make_batch(global_batch_size) -> batch dict``
    supplies data at whatever batch size a candidate needs."""

    def __init__(self, model, base_config: Dict,
                 make_batch: Callable[[int], Dict],
                 example_batch: Optional[Dict] = None,
                 autotuning_config=None, mesh=None):
        from ..runtime.config import AutotuningConfig

        self.model = model
        self.base_config = dict(base_config)
        self.base_config.pop("autotuning", None)
        self.make_batch = make_batch
        self.example_batch = example_batch
        self.cfg = autotuning_config or AutotuningConfig(
            **base_config.get("autotuning", {}))
        self.mesh = mesh
        self.experiments: List[Experiment] = []

    # -- model info (reference: model_info profiling run) -----------------

    def model_info(self) -> Dict[str, Any]:
        import jax

        if getattr(self, "_model_info", None) is not None:
            return self._model_info
        if self.example_batch is None:
            raise ValueError("model_info needs example_batch")
        shapes = jax.eval_shape(
            lambda rngs, b: self.model.init(rngs, **b),
            {"params": jax.random.PRNGKey(0)}, self.example_batch)
        n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
        self._model_info = {"num_params": n}
        return self._model_info

    # -- config space (reference: _generate_experiments) ------------------

    def generate_experiments(self) -> List[Experiment]:
        from ..parallel.topology import build_mesh, get_mesh

        mesh = self.mesh or get_mesh() or build_mesh(
            **self.base_config.get("parallel", {}))
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = shape.get("data", 1) * shape.get("expert", 1)

        base_micro = int(self.base_config.get(
            "train_micro_batch_size_per_gpu",
            max(1, int(self.base_config.get("train_batch_size", dp)) // dp)))
        micros = [base_micro * (2 ** i)
                  for i in range(max(1, self.cfg.num_tuning_micro_batch_sizes))]
        stages = [int(self.base_config.get("zero_optimization", {})
                      .get("stage", 0))] if self.cfg.fast else [0, 1, 2, 3]

        exps = []
        for stage in stages:
            for mb in micros:
                exps.append(Experiment(
                    name=f"z{stage}_mb{mb}",
                    overrides={
                        "zero_optimization": {"stage": stage},
                        "train_micro_batch_size_per_gpu": mb,
                        "gradient_accumulation_steps": 1,
                        "train_batch_size": mb * dp,
                    }))
        return exps

    # -- measurement (reference: scheduler.run_job + metric parse) --------

    def _measure(self, config: Dict, steps: int) -> float:
        import jax

        import deepspeed_tpu as ds
        from ..parallel import topology

        topology.set_mesh(None, None)
        engine, *_ = ds.initialize(model=self.model, config=config,
                                   example_batch=self.example_batch,
                                   mesh=self.mesh)
        batch = self.make_batch(engine.train_batch_size)
        dt = timed_step_seconds(engine, batch, steps)
        if self.cfg.metric == "latency":
            return -dt
        # default "throughput" (samples/sec); "flops" scales by model size
        tput = engine.train_batch_size / dt
        if self.cfg.metric == "flops":
            return tput * self.model_info()["num_params"]
        return tput

    def _run_experiment(self, exp: Experiment, steps: int) -> None:
        config = _merged(self.base_config, exp.overrides)
        try:
            exp.metric_value = self._measure(config, steps)
        except Exception as e:  # candidate failed (OOM, invalid combo...)
            exp.error = f"{type(e).__name__}: {e}"
            logger.debug(traceback.format_exc())
        with open(os.path.join(self.cfg.results_dir, f"{exp.name}.json"),
                  "w") as f:
            json.dump(dataclasses.asdict(exp), f, indent=2)
        log_dist(f"autotune {exp.name}: "
                 f"{exp.metric_value if exp.ok else exp.error}", ranks=[0])

    def _experiment_order(self) -> "list":
        """Evaluation order. ``tuner_type="gridsearch"`` keeps space order;
        ``"model"`` runs the reference's model-based exploration
        (``tuner/model_based_tuner.py``): seed with 2 measurements, then
        repeatedly fit the cost model on everything evaluated so far and
        pick the highest-predicted unevaluated candidate (with every 5th
        pick exploratory, the reference's random_exploration_ratio=0.2 made
        deterministic), so dominated corners of the space are skipped when
        early stopping kicks in."""
        exps = self.experiments
        if self.cfg.tuner_type != "model" or len(exps) <= 2:
            yield from exps
            return
        from .cost_model import RidgeCostModel, config_features, flatten_config

        feats = [config_features(flatten_config(
            _merged(self.base_config, e.overrides))) for e in exps]
        done: List[int] = []
        # seed: first and last of the space (cheapest + most aggressive)
        pending = [0, len(exps) - 1]
        picks = 0
        while True:
            while pending:
                i = pending.pop(0)
                if i not in done:
                    done.append(i)
                    yield exps[i]
            remaining = [i for i in range(len(exps)) if i not in done]
            evaluated_ok = [i for i in done if exps[i].ok]
            if not remaining:
                return
            if len(evaluated_ok) < 2:
                pending.append(remaining[0])
                continue
            picks += 1
            if picks % 5 == 0:  # deterministic exploration slot
                pending.append(remaining[len(remaining) // 2])
                continue
            model = RidgeCostModel().fit(
                [feats[i] for i in evaluated_ok],
                [exps[i].metric_value for i in evaluated_ok])
            pred = model.predict([feats[i] for i in remaining])
            pending.append(remaining[int(np.argmax(pred))])

    def tune_mfu(self, axes: Optional[Dict] = None,
                 budget_evals: Optional[int] = None, steps: int = 3) -> Dict:
        """Drive the full MFU lever space (remat policy x flash tiles x
        loss_chunk x micro/gas split x Pallas-Adam x attention impl) with
        the memoized, cost-model-guided coordinate descent of
        ``mfu_tuner.MFUTuner`` (reference ``tuner/model_based_tuner.py``).
        Requires the model to be one of this framework's config-dataclass
        families (``model.config``)."""
        from .mfu_tuner import MFUTuner

        mcfg = getattr(self.model, "config", None)
        if mcfg is None or not dataclasses.is_dataclass(mcfg):
            raise ValueError(
                "tune_mfu needs a model with a dataclass .config carrying "
                "the lever fields (remat_policy, flash_block_q/k, "
                "loss_chunk, attention_impl)")
        tuner = MFUTuner(type(self.model), mcfg, self.base_config,
                         self.make_batch, axes=axes, mesh=self.mesh,
                         steps=steps, results_dir=self.cfg.results_dir)
        return tuner.tune(budget_evals=budget_evals if budget_evals
                          is not None else self.cfg.tuner_num_trials)

    def tune(self, steps: Optional[int] = None) -> Dict:
        """Run the space; returns the best full config. Writes per-experiment
        results + best_config.json under ``results_dir``."""
        steps = steps if steps is not None else max(
            1, self.cfg.end_profile_step - self.cfg.start_profile_step)
        os.makedirs(self.cfg.results_dir, exist_ok=True)
        best: Optional[Experiment] = None
        stale = 0
        self.experiments = self.generate_experiments()
        for exp in self._experiment_order():
            self._run_experiment(exp, steps)
            if exp.ok and (best is None or exp.metric_value > best.metric_value):
                best, stale = exp, 0
            else:
                stale += 1
                if self.cfg.tuner_early_stopping and \
                        stale >= self.cfg.tuner_early_stopping:
                    break
        if best is None:
            raise RuntimeError(
                f"autotuning: every candidate failed "
                f"({[e.error for e in self.experiments if e.error]})")
        best_config = _merged(self.base_config, best.overrides)
        with open(os.path.join(self.cfg.results_dir, "best_config.json"), "w") as f:
            json.dump({"name": best.name, "metric": self.cfg.metric,
                       "value": best.metric_value, "config": best_config},
                      f, indent=2)
        log_dist(f"autotune best: {best.name} ({self.cfg.metric}="
                 f"{best.metric_value:.1f})", ranks=[0])
        return best_config


def autotune(model, config: Dict, make_batch: Callable[[int], Dict],
             example_batch: Optional[Dict] = None, mesh=None,
             steps: Optional[int] = None, mfu: bool = False,
             axes: Optional[Dict] = None) -> Dict:
    """One-call API (the launcher-level ``--autotuning run`` equivalent,
    reference ``runner.py:323``): tune, then return the winning config ready
    for ``deepspeed_tpu.initialize``. ``mfu=True`` runs the full
    performance-lever search instead (``Autotuner.tune_mfu``; returns its
    richer result dict with ``model_config`` + ``config``)."""
    tuner = Autotuner(model, config, make_batch, example_batch=example_batch,
                      mesh=mesh)
    if mfu:
        # forward the caller's measurement budget to the MFU path too (it
        # was silently dropped before — r5 advisor finding)
        if steps is not None:
            return tuner.tune_mfu(axes=axes, steps=steps)
        return tuner.tune_mfu(axes=axes)
    return tuner.tune(steps=steps)
