"""The training engine.

Counterpart of ``deepspeed/runtime/engine.py:179`` (``DeepSpeedEngine``) and
``deepspeed.initialize`` (``deepspeed/__init__.py:51``). One JSON config drives
precision, optimizer, ZeRO sharding, gradient accumulation, clipping, loss
scaling, monitoring and checkpointing.

TPU-first architecture: instead of wrapping a mutable module with
forward/backward/step methods that issue CUDA work imperatively, the engine
compiles ONE fused ``train_step`` (forward + backward + optimizer update)
under ``jax.jit`` with explicit ``NamedSharding``s for every piece of state.
The ZeRO stage picks those shardings (see ``runtime/zero/partition.py``);
XLA inserts the reduce-scatters/all-gathers that DeepSpeed performs with
hand-written bucketed collectives (``stage_1_and_2.py:895,1216``).

The reference's micro-step API (``engine(batch)`` → ``engine.backward(loss)``
→ ``engine.step()``) is preserved as a thin compatibility layer on top of
``train_batch`` — gradient accumulation happens inside the compiled step via
``lax.scan`` over microbatches (reference: GAS boundary logic
``engine.py:1729,1889``).
"""

import collections
import contextlib
import os
import re
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec

from .. import IMPORT_SECONDS, T_IMPORT
from .. import comm as dist
from ..monitor.tracing import (ENV_TRACE_DIR, FlightRecorder, Tracer,
                               profiler_recording, versioned)
from ..parallel.topology import (BATCH_AXES, SEQ_AXIS, MeshTopology, build_mesh,
                                 get_mesh, set_mesh)
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from .config import DeepSpeedConfig
from .fp16.loss_scaler import (LossScaleState, create_loss_scaler, tree_overflow, update_scale)
from .lr_schedules import get_lr_schedule
from .zero.partition import state_shardings

FORWARD_MICRO_TIMER = "fwd_microstep"
BACKWARD_MICRO_TIMER = "bwd_microstep"
STEP_MICRO_TIMER = "step_microstep"


def load_config_dict(config):
    """Path/dict → config dict, with duplicate-key rejection (reference:
    ``DeepSpeedConfig.__init__`` json loading)."""
    if isinstance(config, (str, os.PathLike)):
        import json as _json

        from .config_utils import dict_raise_error_on_duplicate_keys

        with open(config) as _f:
            return _json.load(_f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
    return config


def _named_scalars(aux) -> Dict[str, jax.Array]:
    """The scalars a training call names beside its loss, ``(loss, {name:
    scalar})``: they leave the fused step with the loss and become registry
    gauges of those names wherever the host fetches it (``models/mixtral.py``
    reports its experts' load so). Any other aux output is dropped."""
    aux = aux[0] if isinstance(aux, tuple) and len(aux) == 1 else aux
    if not isinstance(aux, dict):
        return {}
    return {k: v for k, v in aux.items()
            if k != "loss" and isinstance(v, jax.Array) and v.ndim == 0}


def _param_deltas(aux) -> Dict[str, jax.Array]:
    """What a training call asks to be ADDED to parameters after the
    optimizer's update, ``(loss, {"param_deltas": {flat path: delta}})``:
    buffers that move by a rule of their own and not by a gradient (the
    DeepSeek-V3 router's selection bias, ``models/deepseek_v3.py``)."""
    aux = aux[0] if isinstance(aux, tuple) and len(aux) == 1 else aux
    return dict(aux.get("param_deltas", {})) if isinstance(aux, dict) else {}


def _add_param_deltas(params, deltas):
    found = set()

    def add(kp, p):
        name = _flat_name(kp)
        if name not in deltas:
            return p
        found.add(name)
        return p + deltas[name].astype(p.dtype).reshape(p.shape)

    out = jax.tree_util.tree_map_with_path(add, params)
    if found != set(deltas):
        raise KeyError(f"param_deltas name no parameter: "
                       f"{sorted(set(deltas) - found)}")
    return out


def _freeze_updates(patterns):
    """An optax transformation that zeroes the updates of every parameter
    whose path matches one of ``patterns``; last in a chain, it undoes the
    gradient step and the weight decay alike."""
    import optax

    def update(updates, state, params=None):
        del params
        return jax.tree_util.tree_map_with_path(
            lambda kp, u: jnp.zeros_like(u)
            if any(re.search(p, _flat_name(kp)) for p in patterns) else u,
            updates), state

    return optax.GradientTransformation(lambda _: optax.EmptyState(), update)


def _flat_name(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


class _EngineCheckpointMixin:
    """Model-export paths (reference ``engine.py:3198-3268``)."""

    def module_state_dict(self):
        """Current params as a host pytree (reference ``module_state_dict``)."""
        return jax.device_get(self.state.params)

    def _consolidated_16bit_state_dict(self):
        """Gather params to host at bf16 (reference
        ``_zero3_consolidated_16bit_state_dict`` :3198 — under ZeRO-3 this IS
        the consolidation; device_get gathers every shard)."""
        return jax.tree_util.tree_map(
            lambda p: np.asarray(jax.device_get(p)).astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating) else jax.device_get(p),
            self.state.params)

    def save_16bit_model(self, save_dir: str, output_file: str = "pytorch_model.npz"):
        """Write a consolidated half-precision weights file (reference
        ``save_16bit_model`` :3268). Stored as a flat npz keyed by param path
        (bf16 saved as uint16 bit patterns + a dtype manifest)."""
        os.makedirs(save_dir, exist_ok=True)
        sd = self._consolidated_16bit_state_dict()
        flat = {}
        dtypes = {}
        for kp, leaf in jax.tree_util.tree_flatten_with_path(sd)[0]:
            name = _flat_name(kp)
            arr = np.asarray(leaf)
            if arr.dtype == jnp.bfloat16:
                flat[name] = arr.view(np.uint16)
                dtypes[name] = "bfloat16"
            else:
                flat[name] = arr
                dtypes[name] = str(arr.dtype)
        path = os.path.join(save_dir, output_file)
        np.savez(path, __dtypes__=np.asarray([f"{k}={v}" for k, v in dtypes.items()]),
                 **flat)
        log_dist(f"saved 16-bit model to {path}", ranks=[0])
        return True



@struct.dataclass
class TrainState:
    """All mutable training state, as one donated pytree."""

    step: jnp.ndarray
    params: Any  # master weights (fp32 unless pure half training)
    opt_state: Any
    loss_scale: Optional[LossScaleState]
    skipped_steps: jnp.ndarray


class DeepSpeedEngine(_EngineCheckpointMixin):
    """See module docstring. Construct via ``deepspeed_tpu.initialize``."""

    def __init__(self, model=None, config=None, loss_fn: Optional[Callable] = None,
                 model_parameters=None, example_batch=None, partition_rules=None,
                 optimizer=None, lr_scheduler=None, mesh=None, rng: Optional[jax.Array] = None,
                 dist_init_required: Optional[bool] = None):
        self.module = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.loss_fn = loss_fn
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0

        if dist_init_required is None or dist_init_required:
            dist.init_distributed()

        # ---- config dict (load file path up front so "parallel" can size
        # the mesh before the engine config is built) ----------------------
        config = load_config_dict(config)

        # ---- mesh -------------------------------------------------------
        if mesh is None:
            mesh = get_mesh()
        if mesh is None:
            cfg_parallel = (config or {}).get("parallel", {}) if isinstance(config, dict) else {}
            mesh = build_mesh(**cfg_parallel)
        self.mesh = mesh
        set_mesh(mesh)
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        # batch sharding world: seq-parallel members share samples, so seq is
        # excluded from batch-size accounting (but not from ZeRO sharding).
        # moe.replicate_tokens switches to the pure-EP layout for dense
        # stacked-expert MoE models (tokens replicate across the expert axis;
        # the only in-layer collective is the combine psum — the layout the
        # XLA:CPU thunk runtime can execute inside a layer scan, and the one
        # that avoids per-layer expert-axis batch reshards entirely):
        self._replicate_tokens = bool(
            ((config or {}).get("moe") or {}).get("replicate_tokens", False))
        from ..parallel.topology import set_token_replication

        set_token_replication(self._replicate_tokens)
        self._batch_axes = ("data",) if self._replicate_tokens else BATCH_AXES
        self.dp_world_size = shape.get("data", 1) * (
            1 if self._replicate_tokens else shape.get("expert", 1))
        self.seq_world_size = shape.get("seq", 1)
        self.mp_world_size = shape.get("model", 1)

        # ---- config -----------------------------------------------------
        self._config = DeepSpeedConfig(config, world_size=self.dp_world_size)
        dist.comms_logger.configure(self._config.comms_logger)
        self.train_batch_size = self._config.train_batch_size
        self.micro_batch_size = self._config.train_micro_batch_size_per_gpu
        self.gradient_accumulation_steps = self._config.gradient_accumulation_steps

        # ---- tracing / flight recorder / metrics registry --------------
        # span timelines for the step loop + post-mortem dumps on DS_FAULT
        # firings and checkpoint-verify failures; armed by the config
        # block or the DS_TRACE_DIR env var (monitor/tracing.py). The
        # registry's log-bucket step-latency histogram flows to every
        # monitor backend through MonitorMaster.write_registry. The tracer
        # stands first (it needs only the config): everything the
        # constructor does after it runs under the ``init`` span.
        from ..monitor.perf import PerfAccounting, SetupRecord
        from ..monitor.registry import MetricsRegistry

        tcfg = self._config.tracing
        trace_dir = tcfg.dir or os.environ.get(ENV_TRACE_DIR)
        self.tracer = Tracer(capacity=tcfg.capacity,
                             enabled=bool(tcfg.enabled or trace_dir))
        self.registry = MetricsRegistry()
        self._step_hist = self.registry.histogram("train_batch_s",
                                                  lo=1e-4, hi=4e3)
        #: performance accounting (monitor/perf.py): the compiled train
        #: step registers an argument fingerprint (recompile sentinel —
        #: curriculum/data shape drift shows up as a NAMED alarm, not a
        #: mystery stall) and counts its matrix work once (``StepCost``),
        #: yielding the train_mfu / train_tflops_per_chip gauges.
        self.perf = PerfAccounting(
            tracer=self.tracer, metrics=self.registry, scope="train",
            n_devices=int(np.prod(self.mesh.devices.shape)))
        #: state fingerprint computed once: the TrainState's shapes are
        #: fixed by construction (replace() preserves them) while its
        #: object identity changes every step — re-walking a large param
        #: tree per step would tax the hot loop for a spec that cannot
        #: change. Batch + rng stay fingerprinted per call.
        self._state_spec: Optional[str] = None
        if self.tracer.enabled and tcfg.comm:
            # per-collective observability (comm/comm.py): every
            # all_reduce/all_gather/... staged by the train step emits a
            # comm:<op> span + a comm_op_s{op,dtype,bytes_bucket}
            # histogram — the per-op comm mix trace_view --summary and
            # ds_report aggregate (process-global; last armed engine wins)
            from ..comm.comm import configure_comm_tracing

            configure_comm_tracing(tracer=self.tracer,
                                   registry=self.registry)
        self.flight = None
        if trace_dir:
            self.flight = FlightRecorder(
                trace_dir, self.tracer, last_n=tcfg.flight_events,
                metrics_fn=lambda: {"global_steps": self.global_steps,
                                    **self.registry.snapshot()})
            self.flight.arm_faults()

        #: set-up as numbers (monitor/perf.py SetupRecord): each set-up
        #: span's seconds and the compiles jax reports under it, published
        #: as ``ds.setup`` and the ``setup_*`` gauges (``_publish_setup``)
        self.setup = SetupRecord()
        # the imports on the way here, and from the package's import to
        # here less them: the caller's own work before the engine and,
        # where it starts the backend there (jax has no event for that),
        # backend start
        imports = sum(IMPORT_SECONDS.values())
        self.setup.seconds.update({
            "import": imports,
            "pre_init": time.perf_counter() - T_IMPORT - imports})
        #: whether a profiler session that recorded the last step has the
        #: record already
        self._setup_published = False
        #: choices of the remat rule the compiled step made it take back
        #: (``_fit_train_step``); from the first on the budget is 0
        self._remat_fallbacks = 0
        #: the train step's jaxpr between ``_fit_train_step``'s trace and
        #: ``cost_capture``'s walk of it, None before and after
        self._step_jaxpr = None
        with self.setup.span(self.tracer.span("init", cat="setup")):
            self._construct(model, model_parameters, example_batch,
                            partition_rules, rng)

    def _construct(self, model, model_parameters, example_batch,
                   partition_rules, rng):
        """The rest of the constructor, under the ``init`` span; its
        set-up spans are the children ``init_shapes``, ``init_params``,
        ``init_opt_state`` and ``init_step``. None of them fences: a span
        that ends on a dispatch is host time, and the device's time lands
        in the first wait after it."""
        mesh, setup, tr = self.mesh, self.setup, self.tracer

        # ---- precision --------------------------------------------------
        self.compute_dtype = {"bf16": jnp.bfloat16, "fp16": jnp.float16,
                              "fp32": jnp.float32}[self._config.precision]
        self.fp16_enabled = self._config.fp16.enabled
        self.bfloat16_enabled = self._config.bf16.enabled

        # ---- rng / params ----------------------------------------------
        self._rng = rng if rng is not None else jax.random.PRNGKey(self._config.seed)
        self.example_batch = example_batch
        with setup.span(tr.span("init_shapes", cat="setup")):
            params = model_parameters
            init_fn = init_rngs = None
            if params is None and model is not None and example_batch is not None:
                # Sharded-at-birth init (the real ``zero.Init``): derive shardings
                # from abstract shapes first, then materialize under jit with
                # ``out_shardings`` so no leaf is ever fully resident on one
                # device (reference: ``partition_parameters.py:537`` exists to
                # avoid exactly that replicated birth).
                init_fn, init_args = self._make_init_fn(example_batch)
                params_shapes = jax.eval_shape(init_fn, *init_args)
            elif params is not None:
                params = jax.tree_util.tree_map(
                    lambda p: jnp.asarray(p, jnp.float32)
                    if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating)
                    else jnp.asarray(p), params)
                params_shapes = jax.eval_shape(lambda: params)
            else:
                raise ValueError("Provide model_parameters, or model + example_batch to init")

        # ---- optimizer --------------------------------------------------
        self.lr_scheduler = self._build_lr_scheduler()
        off = self._config.zero_config.offload_optimizer
        self._offload = (off is not None
                         and str(getattr(off.device, "value", off.device)) != "none")
        # fp16 composes with offload since r4: the compiled step produces
        # SCALED grads, the host optimizer unscales + overflow-checks, and
        # the dynamic-scale automaton advances host-side — the reference's
        # default offload mode (``stage_1_and_2.py:1027-1178``).
        opt_cfg = self._config.optimizer
        #: explicit wire-compressed 1-bit path (runtime/onebit_engine.py)
        self._onebit_wire = bool(
            opt_cfg is not None and not self._offload
            and opt_cfg.type.lower() in ("onebitadam", "onebitlamb",
                                         "zerooneadam")
            and (opt_cfg.params or {}).get("comm_backend_name") == "compressed")
        #: explicit bucketed reduce-scatter overlap + ZeRO-1 sharded
        #: update (runtime/zero/overlap.py) — opt-in via
        #: zero_optimization.overlap_grad_sync
        self._overlap_lane = bool(self._config.zero_config.overlap_grad_sync)
        if self._overlap_lane and (self._offload or self._onebit_wire):
            raise ValueError("overlap_grad_sync does not compose with "
                             "offload_optimizer or wire-compressed 1-bit "
                             "training (each owns the explicit grad exchange)")
        self.optimizer = None if (self._offload or self._onebit_wire
                                  or self._overlap_lane) \
            else self._build_optimizer()
        if self.optimizer is None and self._frozen_parameters():
            raise ValueError(
                "a model with frozen parameters needs the fused train step: "
                "offload_optimizer, wire-compressed 1-bit training and "
                "overlap_grad_sync run optimizers of their own")
        if self._config.sparse_gradients_enabled and (self._offload
                                                      or self._onebit_wire
                                                      or self._overlap_lane):
            raise ValueError("sparse_gradients does not compose with "
                             "offload_optimizer, wire-compressed 1-bit "
                             "training, or overlap_grad_sync (each owns the "
                             "explicit grad exchange)")

        # ---- shardings (ZeRO policy) ------------------------------------
        self.param_shardings, shard_opt = state_shardings(
            params_shapes, mesh, self._config.zero_config, partition_rules)
        #: True when params were materialized directly into their shards
        #: (init under jit with out_shardings) rather than placed post-hoc.
        self.params_born_sharded = params is None
        if params is None:
            # trace, compile or cache read, enqueue: the device's time is
            # in the first wait after it
            with setup.span(tr.span("init_params", cat="setup")):
                params = jax.jit(init_fn, out_shardings=self.param_shardings)(*init_args)
        with setup.span(tr.span("init_opt_state", cat="setup")):
            if self._offload or self._onebit_wire or self._overlap_lane:
                self.opt_shardings = ()
            else:
                opt_shapes = jax.eval_shape(self.optimizer.init, params_shapes)
                self.opt_shardings = shard_opt(opt_shapes)
            self._replicated = NamedSharding(mesh, PartitionSpec())

            # ---- build + place state ---------------------------------------
            if self._offload:
                # host owns fp32 master + moments; device holds bf16 weights only
                from .zero.offload import HostOffloadOptimizer

                opt_cfg = self._config.optimizer
                self._host_opt = HostOffloadOptimizer(
                    params,
                    opt_cfg.type if opt_cfg else "AdamW",
                    opt_cfg.params if opt_cfg else {},
                    self._config.zero_config.offload_optimizer,
                    gradient_clipping=self._config.gradient_clipping,
                    lr_scheduler=self.lr_scheduler)
                params = jax.tree_util.tree_map(
                    lambda p, s: jax.device_put(
                        p.astype(self.compute_dtype)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p, s),
                    params, self.param_shardings)
                opt_state = ()
            elif self._onebit_wire or self._overlap_lane:
                self._host_opt = None
                params = jax.tree_util.tree_map(jax.device_put, params, self.param_shardings)
                opt_state = ()  # built by the lane builder below (needs params)
            else:
                self._host_opt = None
                params = jax.tree_util.tree_map(jax.device_put, params, self.param_shardings)
                opt_state = jax.jit(self.optimizer.init,
                                    out_shardings=self.opt_shardings)(params)
        # the scalar leaves are placed on the mesh like every other leaf:
        # the step's outputs carry the mesh in their type, and an initial
        # state that does not would give step 0 a cache key (and a compile)
        # of its own
        on_mesh = lambda x: jax.device_put(x, self._replicated)
        loss_scale = jax.tree_util.tree_map(
            on_mesh, create_loss_scaler(self._config.fp16)) \
            if self.fp16_enabled else None
        self.state = TrainState(step=on_mesh(jnp.zeros([], jnp.int32)),
                                params=params,
                                opt_state=opt_state, loss_scale=loss_scale,
                                skipped_steps=on_mesh(jnp.zeros([], jnp.int32)))
        self.state_shardings = TrainState(
            step=self._replicated, params=self.param_shardings,
            opt_state=self.opt_shardings if not self._offload else (),
            loss_scale=jax.tree_util.tree_map(lambda _: self._replicated, loss_scale),
            skipped_steps=self._replicated)

        # ---- curriculum / PLD ------------------------------------------
        self.curriculum_scheduler = None
        if self._config.curriculum_learning.enabled:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(
                self._config.curriculum_learning)
            # every distinct truncated seqlen is a distinct compiled program
            # (XLA static shapes); warn when a config implies a compile storm
            cs = self.curriculum_scheduler
            if getattr(cs, "difficulties", None) is not None:
                n_shapes = len(set(cs.difficulties))  # fixed_discrete
                knob = "the difficulty list"
            else:
                step = max(1, getattr(cs, "difficulty_step", 1))
                n_shapes = (cs.max_difficulty - cs.min_difficulty) // step + 1
                knob = "difficulty_step"
            if n_shapes > 32:
                logger.warning(
                    f"curriculum_learning implies ~{n_shapes} distinct "
                    f"sequence lengths = {n_shapes} XLA compilations "
                    f"(min={cs.min_difficulty}, max={cs.max_difficulty}). "
                    f"Coarsen {knob} to bound compile time (each distinct "
                    f"length is one program).")
        self._compression = None
        if self._config.compression_config:
            from ..compression.compress import init_compression

            if self._offload:
                raise ValueError("compression_training requires the fused "
                                 "device step (not offload_optimizer)")
            _, self._compression = init_compression(
                None, self._config.compression_config)
        self._moq = None
        if self._config.quantize_training.enabled:
            from .quantize import Quantizer

            if self._offload:
                raise ValueError("quantize_training requires the fused device "
                                 "step (not offload_optimizer)")
            self._moq = Quantizer(self._config.quantize_training)
        self._pld = None
        if self._config.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop

            self._pld = ProgressiveLayerDrop(
                theta=self._config.progressive_layer_drop.theta,
                gamma=self._config.progressive_layer_drop.gamma)
            if self.loss_fn is not None:
                raise ValueError(
                    "progressive_layer_drop drives the model's pld_theta "
                    "input and requires the default model loss path")
            if self._offload:
                raise ValueError(
                    "progressive_layer_drop is not supported with "
                    "offload_optimizer (the host-optimizer grad step does "
                    "not thread pld_theta)")
            import inspect

            sig = inspect.signature(type(self.module).__call__)
            if "pld_theta" not in sig.parameters:
                raise ValueError(
                    f"progressive_layer_drop requires a model accepting "
                    f"pld_theta; {type(self.module).__name__} does not")

        # ---- compiled step ---------------------------------------------
        # [gas, batch, tokens...]: batch over data axes; with sequence
        # parallelism the token dim additionally rides the seq axis
        # (Ulysses/ring resharding happens inside the attention core).
        self.batch_sharding = NamedSharding(mesh,
                                            PartitionSpec(None, self._batch_axes))
        self._batch_seq_sharding = NamedSharding(
            mesh, PartitionSpec(None, self._batch_axes, SEQ_AXIS))
        with setup.span(tr.span("init_step", cat="setup")):
            # (every jitted step's module name carries the trace names' version:
            # ``tracing.versioned``)
            if self._offload:
                self._train_step = None
                self._grad_step = self._compile_grad_step()
            elif self._onebit_wire:
                from .onebit_engine import build_onebit_wire

                if self._moq is not None or self._pld is not None or \
                        self._compression is not None:
                    raise ValueError(
                        "compressed 1-bit training does not compose with "
                        "quantize_training (MoQ), progressive_layer_drop, or "
                        "compression_training; disable those blocks or use the "
                        "optax 1-bit optimizers (no comm_backend_name)")

                opt_state, ob_shardings, step_fn = build_onebit_wire(
                    self, dict(opt_cfg.params or {}), kind=opt_cfg.type.lower())
                self.opt_shardings = ob_shardings
                self.state = self.state.replace(opt_state=jax.device_put(
                    opt_state, ob_shardings))
                self.state_shardings = self.state_shardings.replace(
                    opt_state=ob_shardings)
                self._train_step_fn = step_fn
                self._train_step = jax.jit(
                    versioned(step_fn),
                    in_shardings=(self.state_shardings, None, self._replicated),
                    out_shardings=(self.state_shardings, self._replicated,
                                   self._replicated),
                    donate_argnums=(0,))
            elif self._overlap_lane:
                # bucketed per-layer grad reduce-scatter overlap + data-axis
                # sharded optimizer step (runtime/zero/overlap.py)
                from .zero.overlap import build_overlap_step

                opt_state, ov_shardings, step_fn = build_overlap_step(self)
                self.opt_shardings = ov_shardings
                self.state = self.state.replace(opt_state=jax.device_put(
                    opt_state, ov_shardings))
                self.state_shardings = self.state_shardings.replace(
                    opt_state=ov_shardings)
                self._train_step_fn = step_fn
                self._train_step = jax.jit(
                    versioned(step_fn),
                    in_shardings=(self.state_shardings, None, self._replicated),
                    out_shardings=(self.state_shardings,
                                   (self._replicated,) * 3,
                                   self._replicated),
                    donate_argnums=(0,))
            elif self._config.sparse_gradients_enabled:
                # explicit sparse-gradient DP exchange (runtime/sparse_engine.py;
                # reference sparse_allreduce path, engine.py:2286-2301)
                from .sparse_engine import build_sparse_dp_step

                self.sparse_tensor_module_names, step_fn = \
                    build_sparse_dp_step(self)
                self._train_step_fn = step_fn
                self._sparse_skip_mark = 0  # stall guard, see train_batch
                self._train_step = jax.jit(
                    versioned(step_fn),
                    in_shardings=(self.state_shardings, None, self._replicated),
                    out_shardings=(self.state_shardings,
                                   (self._replicated,) * 3,
                                   self._replicated),
                    donate_argnums=(0,))
            else:
                self._train_step = self._compile_train_step()
        self._eval_step = None

        # ---- timers / monitor ------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size,
                                          steps_per_output=self._config.steps_per_print)
        self.monitor = self._build_monitor()
        self.wall_clock_breakdown = self._config.wall_clock_breakdown

        # micro-step parity API state
        self._pending_microbatches = []
        self._last_loss = None
        #: dispatched steps whose named scalars (``_named_scalars``) nobody
        #: has fetched yet, oldest first: ``(step, loss, {name: scalar})``,
        #: all still on the device. ``_drain_counters`` publishes them; a
        #: step enters only while something listens (``_train_batch``)
        self._counter_queue = collections.deque()
        self._counters_dropped = 0
        #: (host time, step) of the newest publication: two of them give
        #: the rate at which steps complete (the train_mfu gauge)
        self._published = None

        # ---- elastic-agent contract (elasticity/elastic_agent.py) ------
        # under the agent, auto-save periodically into its checkpoint dir
        # and auto-resume from the universal checkpoint the agent converted
        # between incarnations (reference DSElasticAgent restart semantics)
        self._elastic_ckpt_dir = os.environ.get("DS_ELASTIC_CHECKPOINT_DIR")
        if self._elastic_ckpt_dir:
            # NOTE: no heartbeat here by design — the watchdog only judges a
            # rank from its SECOND beat (heartbeat.py), so the restore and
            # first-compile phases are unprotected rather than falsely
            # killed when they outlast the heartbeat timeout
            from ..elasticity.elastic_agent import latest_universal_dir

            uni = latest_universal_dir(self._elastic_ckpt_dir)
            if uni is not None:
                self.load_checkpoint(uni, load_universal=True)
                log_dist(f"elastic auto-resume from {uni} at step "
                         f"{self.global_steps}", ranks=[0])

        log_dist(f"DeepSpeedEngine initialized: precision={self._config.precision}, "
                 f"zero_stage={self._config.zero_optimization_stage}, "
                 f"dp={self.dp_world_size}, mp={self.mp_world_size}, "
                 f"batch={self.train_batch_size} (micro={self.micro_batch_size} x "
                 f"gas={self.gradient_accumulation_steps} x dp={self.dp_world_size})",
                 ranks=[0])

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------

    @staticmethod
    def _make_rngs(base):
        """Per-apply rng collections: dropout + MoE gating noise + PLD layer
        drops (reference: cuda rng tracker / gumbel sampling in
        sharded_moe.py / progressive_layer_drop.py)."""
        if base is None:
            return None
        return {"dropout": base, "gating": jax.random.fold_in(base, 1),
                "pld": jax.random.fold_in(base, 2)}

    def _make_init_fn(self, example_batch):
        """Build (init_fn, args) whose output is the fp32 params tree.

        Used twice: ``jax.eval_shape(init_fn, *args)`` to derive shardings
        with zero materialization, then ``jax.jit(init_fn,
        out_shardings=...)`` so every leaf is born sharded (real
        ``zero.Init``; shard_map-based attention also needs the jit context).
        The batch is a traced argument, not a closure capture — captured
        arrays would be baked into the executable as on-device constants.
        """
        self._rng, init_rng = jax.random.split(self._rng)
        rngs = {"params": init_rng, **self._make_rngs(jax.random.fold_in(init_rng, 7))}

        def init_fn(rngs, batch):
            variables = self.module.init(rngs, **batch)
            params = variables["params"] if "params" in variables else variables
            return jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

        return init_fn, (rngs, example_batch)

    def _build_lr_scheduler(self):
        if self.client_lr_scheduler is not None:
            return self.client_lr_scheduler
        sched_cfg = self._config.scheduler
        if sched_cfg is None or sched_cfg.type is None:
            return None
        return get_lr_schedule(sched_cfg.type, sched_cfg.params)

    def _build_optimizer(self):
        import optax

        if self.client_optimizer is not None:
            tx = self.client_optimizer
        else:
            opt_cfg = self._config.optimizer
            if opt_cfg is None:
                from ..ops.optimizers import FusedAdam

                tx = FusedAdam(self.lr_scheduler or 1e-3)
            else:
                from ..ops.optimizers import get_optimizer

                tx = get_optimizer(opt_cfg.type, opt_cfg.params, self.lr_scheduler, self.mesh)
        clip = self._config.gradient_clipping
        if clip and clip > 0:
            tx = optax.chain(optax.clip_by_global_norm(clip), tx)
        frozen = self._frozen_parameters()
        if frozen:
            tx = optax.chain(tx, _freeze_updates(frozen))
        return tx

    def _frozen_parameters(self):
        """Path patterns of parameters the model declares buffers
        (``type(model).frozen_parameters(config)``): the optimizer's update
        of them is zeroed, so neither a gradient step nor weight decay
        moves them. Most models declare none, and nothing is wrapped."""
        declare = getattr(type(self.module), "frozen_parameters", None)
        config = getattr(self.module, "config", None)
        return list(declare(config)) if declare and config is not None \
            else []

    def _build_monitor(self):
        from ..monitor.monitor import MonitorMaster

        return MonitorMaster(self._config)

    # ------------------------------------------------------------------
    # the compiled train step
    # ------------------------------------------------------------------

    def _default_loss(self, params, batch, rng, **extra):
        """Default loss: model returns scalar loss (HF-style) or (loss, aux).
        ``extra`` carries engine-injected model kwargs (reference: curriculum
        seqlen / PLD state injection, ``engine.py:1636-1650``)."""
        out = self.module.apply({"params": params}, **batch, **extra,
                                rngs=self._make_rngs(rng))
        if isinstance(out, tuple):
            return out[0], out[1:]
        if isinstance(out, dict) and "loss" in out:
            return out["loss"], out
        return out, ()

    def _compile_train_step(self):
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        compute_dtype = self.compute_dtype
        fp16 = self.fp16_enabled
        gas = self.gradient_accumulation_steps
        pld = self._pld
        moq = self._moq
        compression = self._compression

        def compute_loss(params, batch, rng, scale, pld_theta, moq_step=None):
            # loss_fns marked ``casts_params`` (pipeline) cast inside their
            # shard_map region: casting a TP-sharded param before entering a
            # partial-manual shard_map crashes the XLA SPMD partitioner.
            if not getattr(loss_fn, "casts_params", False):
                # the master weights read once a step; the backward's cast
                # of the gradients carries the same name
                with jax.named_scope("ds.param_cast"):
                    params = jax.tree_util.tree_map(
                        lambda p: p.astype(compute_dtype)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p,
                        params)
            if moq is not None and moq_step is not None:
                # MoQ: the COMPUTE weights are fake-quantized on the
                # progressive schedule; fp32 masters stay full precision
                # (reference runtime/quantize.py quantizes the fp16 copies)
                params = moq.quantize_tree(params, moq_step, rng)
            if compression is not None and moq_step is not None:
                # compression scheduler: pruning/quantization masks at this
                # step's intensity (reference engine.py:1620 steps the
                # compression_scheduler during training)
                params = compression.apply(params, moq_step)
            ictx = contextlib.nullcontext()
            if compression is not None and moq_step is not None and \
                    compression.has_activation_methods:
                # activation fake-quant on matched modules' inputs
                # (reference basic_layer.py activation path)
                import flax.linen as fnn

                ictx = fnn.intercept_methods(
                    compression.activation_interceptor(moq_step))
            with ictx:
                if loss_fn is not None:
                    loss, aux = loss_fn(params, batch, rng)
                elif pld_theta is not None:
                    loss, aux = self._default_loss(params, batch, rng,
                                                   pld_theta=pld_theta)
                else:
                    loss, aux = self._default_loss(params, batch, rng)
            return (loss.astype(jnp.float32) * scale,
                    (loss, _named_scalars(aux), _param_deltas(aux)))

        # grads, (loss, named scalars, parameter deltas)
        microbatch_grads = jax.grad(compute_loss, has_aux=True)

        # named like the kernels (ds_*): XLA calls the module after the
        # function, and the module's name — unlike the scopes inside it,
        # which are metadata — is part of the compile cache's key, so an
        # executable cached under other names is never reused
        # (``tracing.versioned`` adds the names' version)
        @versioned
        def ds_train_step(state: TrainState, batch, rng):
            # trace-time side effect: runs once per XLA compile (the
            # compiled-program registry's compile count)
            self.perf.note_compile("train_step")
            scale = state.loss_scale.cur_scale if fp16 else jnp.float32(1.0)
            # PLD keep-rate for THIS step (reference passes pld state into
            # forward each step, engine.py:1636)
            pld_theta = pld.get_theta(state.step) if pld is not None else None
            moq_step = state.step if (moq is not None or
                                      compression is not None) else None

            # ds.* scopes name the two halves of the fused step in a
            # profiler trace (docs/observability.md); metadata only
            with jax.named_scope("ds.loss_and_grad"):
                if gas > 1:
                    rngs = jax.random.split(rng, gas)

                    def body(acc, xs):
                        mb, r = xs
                        g, (loss, named, deltas) = microbatch_grads(
                            state.params, mb, r, scale, pld_theta, moq_step)
                        acc_g, acc_l = acc
                        return (jax.tree_util.tree_map(jnp.add, acc_g, g),
                                acc_l + loss), (named, deltas)

                    zero_g = jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                    (sum_g, sum_loss), (named, deltas) = jax.lax.scan(
                        body, (zero_g, jnp.float32(0.0)), (batch, rngs))
                    grads = jax.tree_util.tree_map(lambda g: g / gas, sum_g)
                    loss = sum_loss / gas
                    named, deltas = jax.tree_util.tree_map(
                        lambda v: v.mean(0), (named, deltas))
                else:
                    squeezed = jax.tree_util.tree_map(lambda x: x[0], batch)
                    grads, (loss, named, deltas) = microbatch_grads(
                        state.params, squeezed, rng, scale, pld_theta,
                        moq_step)

            with jax.named_scope("ds.optimizer"):
                # unscale
                grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
                import optax as _optax

                grad_norm = _optax.global_norm(grads)

                if fp16:
                    overflow = tree_overflow(grads)
                    new_scale = update_scale(state.loss_scale, overflow)
                else:
                    overflow = jnp.bool_(False)
                    new_scale = state.loss_scale

                updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
                new_params = jax.tree_util.tree_map(
                    lambda p, u: p + u.astype(p.dtype), state.params, updates)
                # buffers the model moves by its own rule (mostly none)
                new_params = _add_param_deltas(new_params, deltas)

                # skip the whole update on overflow (reference: _take_model_step
                # engine.py:1889 + CheckOverflow)
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda n, o: jnp.where(overflow, o, n), new, old)
                new_params = keep(new_params, state.params)
                new_opt = keep(new_opt, state.opt_state)

            new_state = state.replace(
                step=state.step + jnp.where(overflow, 0, 1),
                params=new_params,
                opt_state=new_opt,
                loss_scale=new_scale,
                skipped_steps=state.skipped_steps + jnp.where(overflow, 1, 0),
            )
            return new_state, (loss, grad_norm, named), overflow

        # raw Python step kept for the flops profiler's printed tree
        self._train_step_fn = ds_train_step
        return jax.jit(
            ds_train_step,
            # batch shardings follow the device_put placement from
            # _shape_batch (per-leaf: token dims ride the seq axis)
            in_shardings=(self.state_shardings, None, self._replicated),
            out_shardings=(self.state_shardings,
                           (self._replicated,) * 3,
                           self._replicated),
            donate_argnums=(0,),
        )

    def _compile_grad_step(self):
        """Offload mode: the compiled step produces (grads, loss) only; the
        optimizer runs on the host (reference: grads → CPU → DeepSpeedCPUAdam,
        ``stage_1_and_2.py:1027``). Device params are already compute-dtype."""
        loss_fn = self.loss_fn
        gas = self.gradient_accumulation_steps

        def compute_loss(params, batch, rng, scale):
            if loss_fn is not None:
                loss, aux = loss_fn(params, batch, rng)
            else:
                loss, aux = self._default_loss(params, batch, rng)
            # fp16: grads leave the device SCALED (reference scales the loss
            # before backward, ``fp16/loss_scaler.py backward``); the host
            # step divides them back out
            return loss.astype(jnp.float32) * scale, loss

        grad_fn = jax.grad(compute_loss, has_aux=True)

        @versioned
        def ds_grad_step(params, batch, rng, scale):
            self.perf.note_compile("grad_step")
            with jax.named_scope("ds.loss_and_grad"):
                if gas > 1:
                    rngs = jax.random.split(rng, gas)

                    def body(acc, xs):
                        mb, r = xs
                        g, loss = grad_fn(params, mb, r, scale)
                        acc_g, acc_l = acc
                        return (jax.tree_util.tree_map(jnp.add, acc_g, g),
                                acc_l + loss), None

                    zero_g = jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    (sum_g, sum_loss), _ = jax.lax.scan(
                        body, (zero_g, jnp.float32(0.0)), (batch, rngs))
                    grads = jax.tree_util.tree_map(lambda g: g / gas, sum_g)
                    loss = sum_loss / gas
                else:
                    squeezed = jax.tree_util.tree_map(lambda x: x[0], batch)
                    grads, loss = grad_fn(params, squeezed, rng, scale)
            return grads, loss

        return jax.jit(ds_grad_step,
                       in_shardings=(self.param_shardings, None,
                                     self._replicated, self._replicated),
                       out_shardings=(self.param_shardings, self._replicated))

    def _offload_train_batch(self, batch):
        """Host-optimizer step (ZeRO-Offload; with fp16, the reference's
        default composition ``stage_1_and_2.py:1027-1178``: scaled grads →
        host unscale + overflow check → dynamic-scale automaton)."""
        batch = self._shape_batch(batch)
        self._rng, step_rng = jax.random.split(self._rng)
        ls = self.state.loss_scale
        scale = float(jax.device_get(ls.cur_scale)) \
            if (self.fp16_enabled and ls is not None) else 1.0
        grads, loss = self._grad_step(self.state.params, batch, step_rng,
                                      jnp.float32(scale))
        new_params, overflow, grad_norm = self._host_opt.step(
            jax.device_get(grads), loss_scale=scale)
        self._last_grad_norm = grad_norm
        if self.fp16_enabled and ls is not None:
            self.state = self.state.replace(
                loss_scale=update_scale(ls, jnp.bool_(overflow)))
        if overflow:
            self.skipped_steps += 1
            self.state = self.state.replace(
                skipped_steps=self.state.skipped_steps + 1)
        else:
            dev = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(
                    p.astype(self.compute_dtype)
                    if np.issubdtype(p.dtype, np.floating) else p, s),
                new_params, self.param_shardings)
            self.state = self.state.replace(params=dev, step=self.state.step + 1)
        return loss

    # ------------------------------------------------------------------
    # public training API
    # ------------------------------------------------------------------

    def _shape_batch(self, batch: Dict[str, Any]):
        """[train_batch, ...] → [gas, micro*dp, ...] placed on the mesh."""
        gas = self.gradient_accumulation_steps

        def reshape(x):
            x = np.asarray(x) if not isinstance(x, (jnp.ndarray, jax.Array)) else x
            if x.shape[0] == self.train_batch_size:
                x = x.reshape((gas, self.train_batch_size // gas) + x.shape[1:])
            elif x.shape[0] != gas:
                raise ValueError(
                    f"batch leading dim {x.shape[0]} != train_batch_size "
                    f"{self.train_batch_size} (or gas {gas})")
            return x

        batch = {k: reshape(v) for k, v in batch.items()}
        return jax.device_put(batch, self._batch_shardings(batch))

    def _batch_shardings(self, batch):
        """Per-leaf batch shardings: [gas, B, T...] leaves shard tokens over
        seq; [gas, B] leaves (per-sample scalars) shard over batch only."""
        if self.seq_world_size <= 1:
            return jax.tree_util.tree_map(lambda _: self.batch_sharding, batch)
        return jax.tree_util.tree_map(
            lambda x: self._batch_seq_sharding if np.ndim(x) >= 3
            and x.shape[2] % self.seq_world_size == 0 else self.batch_sharding, batch)

    def train_batch(self, data_iter: Optional[Iterator] = None,
                    batch: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
        """One full optimizer step over ``gas`` microbatches.

        Reference: ``PipelineEngine.train_batch`` (``pipe/engine.py:294``) and
        the forward/backward/step loop for the plain engine. Pass either a
        global batch (leading dim = train_batch_size) or an iterator yielding
        microbatches.

        Spans (``monitor/tracing.py``; on the profiler's clock as
        ``ds.<name>``, docs/observability.md): ``train_batch`` holds
        ``data_fetch``, ``shape_batch``, ``observe``, ``dispatch`` (inside
        ``compile`` when the call carries one), ``report`` and
        ``checkpoint_save``; each carries the step's number.
        """
        step = self.global_steps
        with self.tracer.span("train_batch", cat="train", step=step):
            return self._train_batch(data_iter, batch, step)

    def _train_batch(self, data_iter, batch, step: int):
        tr, setup = self.tracer, self.setup
        t_batch0 = time.perf_counter()
        # the first call carries the compile: its parts go into the set-up
        # record; a profiler session's first call publishes the record
        first = not setup.first_step_done
        recording = profiler_recording()
        monitoring = self.monitor is not None and self.monitor.enabled
        printing = self._config.steps_per_print and \
            (step + 1) % self._config.steps_per_print == 0
        reporting = bool(monitoring or printing)
        if batch is None:
            if data_iter is None:
                raise ValueError("train_batch needs a batch or a data iterator")
            with tr.span("data_fetch", cat="train", args={"step": step}):
                micro = [next(data_iter) for _ in range(self.gradient_accumulation_steps)]
                batch = {k: np.concatenate([np.asarray(m[k]) for m in micro]) for k in micro[0]}

        if self.curriculum_scheduler is not None:
            # truncate token dims to this step's difficulty (reference injects
            # curriculum_seqlen into forward, engine.py:1643-1650; here the
            # batch itself is cut, which is the shape XLA compiles). Distinct
            # difficulties are distinct compiled programs — the scheduler's
            # difficulty_step keeps that set small. Batches arrive either
            # [train_batch, T, ...] (token axis 1) or pre-shaped
            # [gas, micro*dp, T, ...] (token axis 2) — see _shape_batch.
            seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps)
            gas = self.gradient_accumulation_steps
            # only KNOWN token-axis fields are cut (a [B, num_classes] field
            # must never be sliced); axis 1 for raw [train_batch, T] batches,
            # axis 2 for pre-shaped [gas, micro*dp, T] batches
            token_fields = {"input_ids", "labels", "attention_mask",
                            "positions", "token_type_ids", "inputs"}

            def cut(k, v):
                if k not in token_fields or np.ndim(v) < 2:
                    return v
                lead = v.shape[0]
                if lead == self.train_batch_size and v.shape[1] > seqlen:
                    return v[:, :seqlen]
                if lead == gas and lead != self.train_batch_size \
                        and np.ndim(v) >= 3 and v.shape[2] > seqlen:
                    return v[:, :, :seqlen]
                return v

            batch = {k: cut(k, np.asarray(v)) for k, v in batch.items()}

        # fault-tolerance hooks: heartbeat for the agent's hang watchdog
        # (written BEFORE the step so staleness ~ time wedged in the step),
        # plus the deterministic DS_FAULT injection points
        ft = self._config.fault_tolerance
        if self._elastic_ckpt_dir and ft.enabled and ft.heartbeat_interval \
                and self.global_steps % ft.heartbeat_interval == 0:
            from ..elasticity.heartbeat import write_heartbeat

            write_heartbeat(self._elastic_ckpt_dir, jax.process_index(),
                            self.global_steps)
        from ..utils.fault_injection import maybe_crash, maybe_stall

        maybe_crash("crash", step=self.global_steps, rank=jax.process_index())
        maybe_stall("stall", step=self.global_steps, rank=jax.process_index())

        if self.wall_clock_breakdown:
            self.timers("train_batch").start()
        self.tput_timer.start()

        if self._offload:
            dispatch = tr.span("dispatch", cat="train", ring="train_step",
                               args={"step": step, "program": "grad_step",
                                     "offload": True})
            with setup.span(dispatch, part="first_dispatch") \
                    if first else dispatch:
                loss = self._offload_train_batch(batch)
            if first:
                setup.seconds["first_step"] = time.perf_counter() - t_batch0
        else:
            with tr.span("shape_batch", cat="host", args={"step": step}):
                batch = self._shape_batch(batch)
                self._rng, step_rng = jax.random.split(self._rng)
            fp = self._config.flops_profiler
            profiling = (fp.enabled and self.global_steps == fp.profile_step)
            t0 = time.perf_counter() if profiling else None
            # recompile sentinel: the train step is a RESIDENT program —
            # a fingerprint change (curriculum seqlen, drifting data
            # shapes) is a compile stall and gets a named alarm. The
            # state spec is computed once (shapes fixed by construction).
            from ..monitor import perf as _perf

            with tr.span("observe", cat="host", args={"step": step}):
                if self._state_spec is None:
                    self._state_spec = _perf.spec(self.state)
                recompiled = self.perf.programs.observe_call(
                    "train_step", {"state": self._state_spec,
                                   "batch": _perf.spec(batch),
                                   "rng": _perf.spec(step_rng)}) is not None
                warm = not self.perf.programs.program(
                    "train_step").cost_pending
            # the span covers the DISPATCH of the fused fwd/bwd/optimizer
            # program (its halves are the ds.loss_and_grad / ds.optimizer
            # scopes of the device trace) — forcing the loss here would
            # fence the device every step just to trace. A call known to
            # carry a compile (the first, or one the sentinel flagged)
            # sits inside a compile span, so an idle device is explained
            compiling = setup.span(tr.span(
                "compile", cat="host",
                args={"step": step, "program": "train_step"}),
                part="first_dispatch") \
                if (recompiled or not warm) else contextlib.nullcontext()
            with compiling, tr.span(
                    "dispatch", cat="train", ring="train_step",
                    args={"step": step, "program": "train_step"}):
                if recompiled or not warm:
                    # the compile, ahead of the call that would carry it
                    self._fit_train_step(batch, step_rng)
                self.state, (loss, self._last_grad_norm, named), overflow = \
                    self._train_step(self.state, batch, step_rng)
            # the step's named scalars wait on the device for a later call
            # to publish them — only while something listens: the ring, a
            # monitor, a progress line, or a recording profiler
            if not warm or reporting or tr.enabled or recording:
                self._queue_counters(step, loss, named)
            if self._counter_queue:
                # after the dispatch: the device has work again, so the
                # fetch hides under it. The compile-carrying call has just
                # waited seconds: one step's wait is the cheapest there is
                # (the first step's execution, where the step names scalars
                # to fetch: the set-up record's first_wait_s)
                self._drain_counters(wait=not warm)
            if first:
                setup.seconds["first_step"] = time.perf_counter() - t_batch0
            if not warm:
                # once, after the compile-carrying first call: the matrix
                # work of the step by scope, walked off the jaxpr its
                # lowering was made from (``_fit_train_step`` kept it)
                with setup.span(tr.span("cost_capture", cat="host",
                                        args={"step": step})):
                    self.perf.capture_step_cost("train_step",
                                                self._step_jaxpr)
                    self._step_jaxpr = None
            if profiling:
                float(loss)  # device fence so the measured latency is real
                self._print_flops_profile(batch, step_rng,
                                          time.perf_counter() - t0)

        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps
        if self._config.sparse_gradients_enabled and self.global_steps % 16 == 0:
            # sparse capacity overflows skip the step but (unlike fp16 loss
            # scaling) never self-heal: if EVERY step of the window was
            # skipped, training is stalled — fail loudly (the reference torch
            # path errors on the sparse+dense grad mix; see sparse_engine)
            skipped = self.get_skipped_steps()
            if skipped - self._sparse_skip_mark >= 16:
                raise RuntimeError(
                    "sparse_gradients: the last 16 optimizer steps were ALL "
                    "skipped by sparse-capacity overflow — an embedding in "
                    "the sparse set receives dense gradients (tied embedding"
                    "/vocab projection?). Disable sparse_gradients or untie "
                    "the offending leaf.")
            self._sparse_skip_mark = skipped
        if self._elastic_ckpt_dir and self.global_steps % \
                max(1, self._config.elasticity.save_interval) == 0:
            self.save_checkpoint(self._elastic_ckpt_dir)
            self._prune_elastic_checkpoints(keep=max(1, ft.keep_checkpoints))
        self.tput_timer.stop()
        if self.wall_clock_breakdown:
            self.timers("train_batch").stop()
        self._step_hist.observe(time.perf_counter() - t_batch0)

        if reporting:
            # both fetch the loss, so this span is the host's wait on
            # the device when either is on
            with tr.span("report", cat="host", args={"step": step}):
                self._drain_counters(wait=True)
                if monitoring:
                    self._write_monitor(loss)
                if printing:
                    self._report_progress(loss)
        if first or (recording and not self._setup_published):
            self._publish_setup(step, first)
        self._setup_published = recording
        self._last_loss = loss
        return loss

    def _prune_elastic_checkpoints(self, keep: int) -> None:
        """The engine owns the elastic auto-save cadence, so it must also own
        the disk: keep the newest ``keep`` snapshots — but never delete the
        newest *verified* save, the job's only guaranteed way back when a
        newer save turns out partial/corrupt (checkpoint/manifest.py)."""
        if jax.process_index() != 0:
            return
        from ..checkpoint.manifest import prune_checkpoints

        prune_checkpoints(self._elastic_ckpt_dir, keep=keep)

    def _print_flops_profile(self, shaped_batch, rng, step_time_s):
        """Flops-profiler hook (reference ``engine.py:1615,1634``: start at
        ``profile_step``, print, stop)."""
        from ..profiling.flops_profiler.profiler import FlopsProfiler

        fp = self._config.flops_profiler
        prof = FlopsProfiler(self)
        prof.profile_step(shaped_batch, rng)
        prof.step_time_s = step_time_s
        out = open(fp.output_file, "w") if fp.output_file else None
        try:
            prof.print_model_profile(module_depth=fp.module_depth,
                                     top_modules=fp.top_modules if not fp.detailed
                                     else 0, file=out)
        finally:
            if out is not None:
                out.close()
        self._flops_profile = prof  # exposed for tests / callers

    # -- reference micro-step parity API --------------------------------

    def forward(self, batch: Dict[str, Any]):
        """Parity: ``engine(batch)`` queues a global microbatch
        (leading dim = micro_batch_size * dp) and returns a LAZY loss.

        The fused computation happens at the GAS boundary in ``step()``; the
        returned loss only runs a (single) eval forward if the caller actually
        forces its value (``float(loss)``), so the normal
        forward/backward/step loop costs no extra FLOPs.
        """
        self._pending_microbatches.append(batch)
        return _LazyLoss(self, batch)

    __call__ = None  # set below

    def backward(self, loss=None, **_):
        """Parity no-op: grads are computed inside the fused step (XLA AD).
        Reference: ``engine.backward`` :1750."""
        return loss

    def step(self):
        """Parity: consume queued microbatches and take the optimizer step.
        Each queued microbatch is a *global* microbatch (micro * dp samples).
        Reference: ``engine.step`` :1957."""
        if len(self._pending_microbatches) < self.gradient_accumulation_steps:
            return  # not at a GAS boundary yet (reference gates the same way)
        micro = self._pending_microbatches[:self.gradient_accumulation_steps]
        self._pending_microbatches = self._pending_microbatches[
            self.gradient_accumulation_steps:]
        batch = {k: np.concatenate([np.asarray(m[k]) for m in micro]) for k in micro[0]}
        return self.train_batch(batch=batch)

    def _compile_eval_step(self):
        def eval_step(params, batch, rng, step):
            half = jax.tree_util.tree_map(
                lambda p: p.astype(self.compute_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
            # eval must see the SAME weight transforms as training (reference
            # compressed modules mask in eval forward too) — otherwise
            # pruning/quantization degradation is invisible until export
            if self._moq is not None:
                half = self._moq.quantize_tree(half, step, rng)
            if self._compression is not None:
                half = self._compression.apply(half, step)
            if self.loss_fn is not None:
                loss, _ = self.loss_fn(half, batch, rng)
            else:
                loss, _ = self._default_loss(half, batch, rng)
            return loss

        return jax.jit(eval_step, in_shardings=(
            self.param_shardings,
            NamedSharding(self.mesh, PartitionSpec(self._batch_axes)),
            self._replicated, self._replicated), out_shardings=self._replicated)

    def eval_batch(self, batch: Dict[str, Any]):
        if self._eval_step is None:
            self._eval_step = self._compile_eval_step()
        mb = jax.device_put(
            batch, NamedSharding(self.mesh, PartitionSpec(self._batch_axes)))
        # fixed rng: eval losses are reproducible call-to-call (stochastic
        # layers like MoE gating see the same noise for the same batch)
        return self._eval_step(self.state.params, mb,
                               jax.random.PRNGKey(self._config.seed),
                               self.state.step)

    # ------------------------------------------------------------------
    # introspection (reference config accessor properties engine.py:466-788)
    # ------------------------------------------------------------------

    @property
    def config(self) -> DeepSpeedConfig:
        return self._config

    def zero_optimization_stage(self) -> int:
        return self._config.zero_optimization_stage

    def get_global_grad_norm(self):
        """Global (pre-clip) grad L2 norm of the LAST step (reference
        monitoring contract, ``engine.get_global_grad_norm``). The fused
        step computes it on device; fetching forces only a scalar. Returns
        None for skipped (overflow) steps — their norm is inf/NaN and the
        reference reports nothing for them either."""
        if getattr(self, "_last_grad_norm", None) is None:
            return None
        norm = float(jax.device_get(self._last_grad_norm))
        return norm if np.isfinite(norm) else None

    @property
    def loss_scale(self):
        if self.state.loss_scale is None:
            return 1.0
        return float(jax.device_get(self.state.loss_scale.cur_scale))

    def get_lr(self):
        if self.lr_scheduler is None:
            opt = self._config.optimizer
            return [opt.params.get("lr", 1e-3) if opt else 1e-3]
        return [float(jax.device_get(jnp.asarray(
            self.lr_scheduler(self.state.step))))]

    def get_skipped_steps(self) -> int:
        return int(jax.device_get(self.state.skipped_steps))

    # ------------------------------------------------------------------
    # monitoring
    # ------------------------------------------------------------------

    #: steps ``_counter_queue`` holds at most; beyond it the oldest goes
    #: unpublished and the next ``counters`` span says how many did
    COUNTER_QUEUE_BOUND = 64

    def _queue_counters(self, step, loss, named):
        queue = self._counter_queue
        queue.append((step, loss, named))
        if len(queue) > self.COUNTER_QUEUE_BOUND:
            queue.popleft()
            self._counters_dropped += 1

    def _drain_counters(self, wait: bool = False):
        """Publish the queued steps the device has finished (all of them
        with ``wait``, which blocks: only where the host waits anyway): one
        ``device_get`` for their named scalars, then per step a ``counters``
        span that carries them (``ds.counters`` on the profiler's clock, a
        ring event) and registry gauges of their names. Steps complete in
        order, so the first loss that is not ready ends the search."""
        queue = self._counter_queue
        n = len(queue)
        if not wait:
            n = next((i for i, (_, loss, _) in enumerate(queue)
                      if not loss.is_ready()), n)
        if not n:
            return
        done = [queue.popleft() for _ in range(n)]
        values = None
        for i, (step, _, _) in enumerate(done):
            args = {"step": step}
            if self._counters_dropped:
                args["dropped"] = self._counters_dropped
                self._counters_dropped = 0
            with self.tracer.span("counters", cat="train", args=args) as sp:
                if values is None:      # the first span holds the fetch
                    values = jax.device_get([named for _, _, named in done])
                scalars = {k: float(v) for k, v in values[i].items()}
                sp.set(**scalars)
                for name, value in scalars.items():
                    self.registry.gauge(name).set(value)
            if i == 0 and not self.setup.first_step_done:
                self.setup.seconds["first_wait"] = sp.seconds
        self._publish_step_rate(time.perf_counter(), done[-1][0])

    def _publish_step_rate(self, now: float, step: int):
        """``train_mfu`` / ``train_tflops_per_chip``: the matrix operations
        the step runs, replays left out (``StepCost.model_flops``), over the
        time a step took: steps published since the last
        publication ÷ the host time between the two. A publication follows
        the device (it waits, or finds finished what a fence finished), so
        the interval is device-paced however far ahead the host dispatches;
        the wall clock of one ``train_batch`` call is not. The first
        publication only starts the clock (it follows the compile)."""
        last, self._published = self._published, (now, step)
        if last is None or step <= last[1] or \
                self.perf.programs.program("train_step").cost_source is None:
            return
        vals = self.perf.on_program_step(
            "train_step", (now - last[0]) / (step - last[1]))
        if vals["mfu"] is not None:
            self.registry.gauge("train_mfu").set(vals["mfu"])
        if vals["flops_per_sec"]:
            self.registry.gauge("train_tflops_per_chip").set(
                vals["flops_per_sec"] / 1e12 / self.perf.n_devices)

    def _write_monitor(self, loss):
        events = [
            ("Train/Samples/train_loss", float(jax.device_get(loss)),
             self.global_steps * self.train_batch_size),
            ("Train/Samples/lr", self.get_lr()[0],
             self.global_steps * self.train_batch_size),
        ]
        if self.fp16_enabled:
            events.append(("Train/Samples/loss_scale", self.loss_scale,
                           self.global_steps * self.train_batch_size))
        gn = self.get_global_grad_norm()
        if gn is not None:
            events.append(("Train/Samples/grad_norm", gn,
                           self.global_steps * self.train_batch_size))
        self.monitor.write_events(events)
        # the unified registry (step/checkpoint latency histograms) rides
        # the same backends — one bridge, no backend changes
        self.monitor.write_registry(self.registry, self.global_steps,
                                    prefix="Train/Registry/")

    def _report_progress(self, loss):
        log_dist(f"step={self.global_steps}, skipped={self.get_skipped_steps()}, "
                 f"lr={self.get_lr()}, loss={float(jax.device_get(loss)):.6f}",
                 ranks=[0])

    # ------------------------------------------------------------------
    # checkpointing (full engine in checkpoint/; basic save/load here)
    # ------------------------------------------------------------------

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None, save_latest: bool = True):
        """Reference: ``engine.save_checkpoint`` :2881."""
        from ..checkpoint.engine import save_train_state

        tag = tag or f"global_step{self.global_steps}"
        client_state = dict(client_state or {})
        client_state.update(global_steps=self.global_steps,
                            skipped_steps=self.get_skipped_steps())
        ft = self._config.fault_tolerance
        t_save0 = time.perf_counter()
        # checkpoint I/O is the step loop's big non-compute latency — a
        # traced run shows exactly which steps paid it
        with self.tracer.span("checkpoint_save", cat="checkpoint",
                              args={"step": self.global_steps, "tag": tag}):
            if self._offload:
                # host-side fp32 masters + moments live outside TrainState;
                # written BEFORE the manifest so the save's integrity check
                # covers them too
                os.makedirs(save_dir, exist_ok=True)
                sd = self._host_opt.state_dict()
                np.savez(os.path.join(save_dir, f"{tag}.host_optimizer.npz"),
                         step=sd["step"],
                         **{f"master_{i}": m for i, m in enumerate(sd["master"])},
                         **{f"moment_{mi}_{li}": buf
                            for mi, bank in enumerate(sd["moments"])
                            for li, buf in enumerate(bank)})
            save_train_state(save_dir, tag, self.state, client_state,
                             save_latest=save_latest,
                             save_retries=ft.save_retries if ft.enabled else 0,
                             retry_backoff_s=ft.save_retry_backoff,
                             manifest_checksums=ft.manifest_checksums)
        self.registry.histogram("checkpoint_save_s", lo=1e-3,
                                hi=4e3).observe(time.perf_counter() - t_save0)
        return True

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_universal: Optional[bool] = None, **_):
        """Reference: ``engine.load_checkpoint`` :2531. With
        ``load_universal`` (arg or ``checkpoint.load_universal`` config,
        reference ``engine.py:740``) ``load_dir`` is a universal checkpoint
        directory (see ``checkpoint/universal.py``) loadable at ANY
        mesh/parallelism."""
        if load_universal is None:
            load_universal = self._config.load_universal_checkpoint
        if load_universal:
            from ..checkpoint.universal import restore_into

            state, meta = restore_into(
                self.state, self.state_shardings, load_dir,
                load_optimizer_states=load_optimizer_states)
            self.state = state
            client_state = meta.get("client_state", {})
            self.global_steps = int(client_state.get("global_steps",
                                                     meta.get("step") or 0))
            if self._offload:
                # universal checkpoints carry no host-optimizer banks: rebuild
                # the fp32 masters straight from the checkpoint's fp32 arrays
                # (NOT the bf16 device params — that would launder the master
                # through 8 mantissa bits) and reset moments + step count
                from ..checkpoint.universal import _flat_name, load_universal

                flat, _ = load_universal(load_dir)
                leaves = []
                for kp, leaf in jax.tree_util.tree_flatten_with_path(
                        state.params)[0]:
                    name = "params/" + _flat_name(kp)
                    leaves.append(
                        np.asarray(flat[name], np.float32) if name in flat
                        else np.asarray(jax.device_get(leaf), np.float32))
                self._host_opt.reset_optimizer_state(leaves)
                log_dist("[load_checkpoint] universal restore on an offload "
                         "engine: fp32 masters copied from the checkpoint, "
                         "optimizer moments reset", ranks=[0])
            if hasattr(self, "_sparse_skip_mark"):
                self._sparse_skip_mark = self.get_skipped_steps()
            return load_dir, client_state
        from ..checkpoint.engine import load_train_state
        from ..checkpoint.manifest import resolve_load_tag

        ft = self._config.fault_tolerance
        if ft.enabled and ft.verify_on_load:
            # resolve+verify once up front (fallback walk on corrupt/partial
            # saves) so the offload sidecar below agrees with the restored
            # tag; load_train_state then takes the concrete tag as-is
            try:
                tag = resolve_load_tag(load_dir, tag)
            except Exception as e:
                # a verify failure with NO loadable fallback is an
                # incident: leave a post-mortem before propagating.
                # manifest.py already dumps through the process-global
                # recorder (it has no engine handle), so only dump here
                # when no global recorder is armed — one incident, one dump
                from ..monitor.tracing import default_flight_recorder
                if (self.flight is not None
                        and default_flight_recorder() is None):
                    self.flight.record("checkpoint_verify",
                                       {"dir": load_dir, "tag": tag,
                                        "error": str(e)})
                raise
        state, client_state = load_train_state(
            load_dir, tag, self.state, self.state_shardings,
            load_optimizer_states=load_optimizer_states, verify=False)
        self.state = state
        self.global_steps = int(client_state.get("global_steps", 0))
        if self._offload:
            if tag is None:
                with open(os.path.join(load_dir, "latest")) as f:
                    tag = f.read().strip()
            host_path = os.path.join(load_dir, f"{tag}.host_optimizer.npz")
            if load_optimizer_states and os.path.exists(host_path):
                z = np.load(host_path)
                n = len(self._host_opt.master)
                nbanks = len(self._host_opt._moments)
                self._host_opt.load_state_dict({
                    "step": int(z["step"]),
                    "master": [z[f"master_{i}"] for i in range(n)],
                    "moments": [[z[f"moment_{mi}_{li}"] for li in range(n)]
                                for mi in range(nbanks)],
                })
            else:
                # no host state to restore: rebuild masters from the loaded
                # device params (best source this checkpoint has) and reset
                # moments so the next step doesn't apply stale state
                self._host_opt.reset_optimizer_state(
                    jax.tree_util.tree_leaves(jax.device_get(state.params)))
        if hasattr(self, "_sparse_skip_mark"):
            self._sparse_skip_mark = self.get_skipped_steps()
        return load_dir, client_state

    def _publish_setup(self, step: int, first: bool) -> None:
        """Publish the set-up record (``monitor/perf.py SetupRecord``): the
        ``setup_*`` registry gauges, and one ``setup`` span that carries
        every number — ``ds.setup`` on the profiler's clock inside
        ``ds.train_batch``, where a traced run and a ``/profilez`` capture
        find it (the benchmark's ``setup.*`` readers read it there), and a
        ring event. Once after the first step, which ends set-up (the record
        stands from there on), and on the first step of every profiler
        session. Beside it, the same way, the train step's matrix work
        (``monitor/perf.py StepCost``) as one ``step_cost`` span."""
        if first:
            self.setup.close()
        record = self.setup.record(step)
        for name, value in record.items():
            self.registry.gauge(f"setup_{name}").set(value)
        with self.tracer.span("setup", cat="setup", args=record):
            pass
        cost = self.perf.programs.program("train_step").step_cost
        if cost is not None:
            with self.tracer.span("step_cost", cat="setup",
                                  args=cost.record()):
                pass

    def _remat_budget(self) -> Tuple[int, Optional[Tuple[int, int]]]:
        """``(the bytes the remat rule may plan with, a device's (limit, in
        use) or None)``: ``layers.REMAT_SHARE`` of ONE device's memory less
        what is in use there now, the state resident -- whatever the mesh:
        the model files count a device's part of what they offer
        (``layers.device_part``), and ``memory_analysis()`` behind them is a
        device's too. 0 where the numbers cannot be read (a CPU), on the
        one-bit, overlap and sparse lanes (their steps differentiate by their
        own rules), and once a choice was taken back."""
        from ..models import layers

        memory = _device_memory(self.mesh.local_devices[0])
        if memory is None or self._remat_fallbacks or self._onebit_wire or \
                self._overlap_lane or self._config.sparse_gradients_enabled:
            return 0, memory
        limit, in_use = memory
        return max(int(layers.REMAT_SHARE * limit) - in_use, 0), memory

    def _fit_train_step(self, batch, rng) -> None:
        """Lower and compile the train step for this batch AHEAD of the call
        (the call then finds the executable: nothing is lowered or compiled
        twice), its trace under the budget ``_remat_budget`` states -- the
        model's remat'ed blocks keep, of the values they offer by name, what
        fits it (``layers.keep_for_room``). Then the check on the compiled
        program: where something was kept and the step's footprint
        (``memory_analysis()``'s peak, arguments included) plus what else the
        process holds on the device stands over ``layers.REMAT_MARGIN`` of
        the device's memory, or the compiler refused the step for memory,
        the step is built once more with nothing kept -- the state is not
        yet donated, no step of this shape has run -- and one line says so.
        The numbers go to the set-up record (``SetupRecord.COUNTS``)."""
        from ..models import layers

        def build(budget):
            with layers.remat_room(budget) as kept:
                traced = self._train_step.trace(self.state, batch, rng)
                lowered = traced.lower()
            if self.perf.programs.program("train_step").cost_pending:
                # the one trace: ``cost_capture`` walks it after the step
                self._step_jaxpr = traced.jaxpr
            try:
                return dict(kept), lowered.compile().memory_analysis()
            except jax.errors.JaxRuntimeError as e:
                if not kept or "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                return dict(kept), None

        budget, memory = self._remat_budget()
        kept, compiled = build(budget)
        size = lambda name: int(getattr(compiled, name, 0) or 0)
        if kept:
            limit, in_use = memory
            footprint = None if compiled is None else \
                size("peak_memory_in_bytes") + max(
                    in_use - size("argument_size_in_bytes"), 0)
            if footprint is None or footprint > layers.REMAT_MARGIN * limit:
                logger.warning(
                    f"train step: kept {sorted(kept)} ({sum(kept.values())} "
                    f"bytes) for a budget of {budget} bytes, and the compiled "
                    f"step " + ("was refused for memory" if footprint is None
                                else f"stands at {footprint} of {limit} "
                                f"bytes") + ": built again with nothing kept")
                self._remat_fallbacks += 1
                self._train_step = self._compile_train_step()
                kept, compiled = build(0)
        self.setup.counts.update(
            remat_kept_bytes=sum(kept.values()), remat_kept_names=len(kept),
            remat_room_bytes=budget, remat_fallbacks=self._remat_fallbacks,
            step_argument_bytes=size("argument_size_in_bytes"),
            step_temp_bytes=size("temp_size_in_bytes"),
            step_peak_bytes=size("peak_memory_in_bytes"))
        self.perf.programs.program("train_step").memory = dict(
            self.setup.counts)
        if memory is not None:
            log_dist(
                f"train step: kept {sorted(kept) or 'nothing'} "
                f"({sum(kept.values()) / 1e9:.2f} GB of {budget / 1e9:.2f} GB "
                f"room); compiled: arguments "
                f"{size('argument_size_in_bytes') / 1e9:.2f} + temp "
                f"{size('temp_size_in_bytes') / 1e9:.2f} GB, peak "
                f"{size('peak_memory_in_bytes') / 1e9:.2f} of "
                f"{memory[0] / 1e9:.2f} GB", ranks=[0])


class _LazyLoss:
    """Loss handle returned by the parity ``forward``: forcing it (float/
    array) runs one eval forward; passing it straight to ``backward`` costs
    nothing."""

    def __init__(self, engine: DeepSpeedEngine, batch):
        self._engine = engine
        self._batch = batch
        self._value = None

    def _force(self):
        if self._value is None:
            self._value = self._engine.eval_batch(self._batch)
        return self._value

    def __float__(self):
        return float(jax.device_get(self._force()))

    def __jax_array__(self):
        return jnp.asarray(self._force())

    def __repr__(self):
        return f"LazyLoss({float(self) if self._value is not None else 'unevaluated'})"


DeepSpeedEngine.__call__ = DeepSpeedEngine.forward


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mpu=None, dist_init_required=None,
               collate_fn=None, config=None, config_params=None, loss_fn=None,
               example_batch=None, partition_rules=None, mesh=None, rng=None
               ) -> Tuple[DeepSpeedEngine, Any, Any, Any]:
    """Reference: ``deepspeed.initialize`` (``deepspeed/__init__.py:51``).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)``. ``optimizer``
    slot returns the engine itself (the optax transformation is internal);
    ``dataloader`` is built when ``training_data`` is given.
    """
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and getattr(args, "deepspeed_config", None):
        config = args.deepspeed_config

    from ..pipe.module import PipelineModule

    if isinstance(model, PipelineModule):
        # reference dispatches PipelineModule → PipelineEngine
        # (deepspeed/__init__.py:126-146)
        from ..pipe.engine import PipelineEngine

        unsupported = {"model_parameters": model_parameters, "loss_fn": loss_fn,
                       "partition_rules": partition_rules}
        bad = [k for k, v in unsupported.items() if v is not None]
        if bad:
            raise ValueError(
                f"initialize(model=PipelineModule) does not accept {bad}: the "
                "pipeline module owns its params/loss/partitioning (use "
                "engine.load_checkpoint to restore weights)")
        cfg_dict = load_config_dict(config) or {}
        from .zero.config import DeepSpeedZeroConfig

        _zcfg = DeepSpeedZeroConfig(**(cfg_dict.get("zero_optimization") or {}))
        if _zcfg.offload_param is not None and \
                _zcfg.offload_param.device != "none" and model.num_stages == 1:
            # param swapping: layer list streamed through the device
            # (reference: ZeRO-Infinity offload_param → param swapper).
            # Multi-stage pipelines keep the PipelineEngine path (streamed
            # params + the pipe ring is future work; offload_param there is
            # the reference's compat no-op).
            from .zero.infinity import ZeroInfinityEngine

            if optimizer is not None:
                raise ValueError("ZeroInfinityEngine builds its own host "
                                 "optimizer from the config; a client "
                                 "optimizer is not supported with "
                                 "offload_param")
            engine = ZeroInfinityEngine(model, config=cfg_dict,
                                        example_batch=example_batch, rng=rng,
                                        lr_scheduler=lr_scheduler, mesh=mesh)
        else:
            engine = PipelineEngine(model=model, config=config,
                                    example_batch=example_batch,
                                    mesh=mesh, rng=rng, optimizer=optimizer,
                                    lr_scheduler=lr_scheduler,
                                    dist_init_required=dist_init_required)
    else:
        engine = DeepSpeedEngine(model=model, config=config, loss_fn=loss_fn,
                                 model_parameters=model_parameters,
                                 example_batch=example_batch,
                                 partition_rules=partition_rules, optimizer=optimizer,
                                 lr_scheduler=lr_scheduler, mesh=mesh, rng=rng,
                                 dist_init_required=dist_init_required)

    dataloader = None
    if training_data is not None:
        from .dataloader import DeepSpeedDataLoader

        # One SPMD process feeds the GLOBAL microbatch (micro * dp samples),
        # unlike the reference where each rank loads micro samples.
        dataloader = DeepSpeedDataLoader(
            training_data, batch_size=engine.micro_batch_size * engine.dp_world_size,
            collate_fn=collate_fn)
    return engine, engine, dataloader, engine.lr_scheduler


def _device_memory(device) -> Optional[Tuple[int, int]]:
    """``(bytes_limit, bytes_in_use)`` of the device's allocator, or None
    where the backend keeps no such numbers (a CPU)."""
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]), int(stats.get("bytes_in_use", 0))
