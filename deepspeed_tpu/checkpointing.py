"""`deepspeed.checkpointing` facade — the user-callable activation
checkpointing API.

Reference: ``deepspeed/runtime/activation_checkpointing/checkpointing.py``
exposes ``configure(...)`` (:825) and ``checkpoint(function, *args)`` (:743)
as a drop-in for ``torch.utils.checkpoint`` — Megatron-style integrations
call these directly around transformer blocks.

TPU translation: ``checkpoint`` wraps the function in ``jax.checkpoint``
(rematerialization — identical semantics: forward activations dropped,
recomputed during backward). The reference's memory knobs map as:

- ``checkpoint_in_cpu`` -> host-offload remat policy (saved residuals live
  in pinned host memory; XLA schedules the device<->host copies — the
  reference's explicit ``.cpu()`` round-trips, compiler-scheduled);
- ``partition_activations`` -> accepted no-op: under SPMD the partitioner
  already shards saved activations with the mesh, which is the state this
  flag exists to reach on torch;
- ``contiguous_checkpointing`` -> accepted no-op: XLA's buffer assignment
  owns layout; there is no allocator fragmentation for the flag to fix;
- ``synchronize`` -> accepted no-op (a device fence per checkpoint call
  would serialize the async dispatch pipeline; see docs/design_notes.md
  timing discipline);
- ``profile`` -> logs wall time per checkpointed call (enqueue-side).

RNG helpers (``model_parallel_cuda_manual_seed`` etc.) keep Megatron
integrations importable: under SPMD every device executes the same program
with ``jax.random`` keys threaded explicitly, so the tracker stores seeds
for parity rather than device RNG state.
"""

import time
from typing import Any

import jax

from .models.layers import resolve_remat_policy
from .utils.logging import log_dist

_config = {
    "configured": False,
    "policy": "nothing",          # classic torch-checkpoint semantics
    "profile": False,
    "num_checkpoints": None,
    "mpu": None,
    "seed": None,
}


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Reference signature (``checkpointing.py:825``); see module docstring
    for the TPU meaning of each knob."""
    if deepspeed_config is not None:
        import json

        from .runtime.config import ActivationCheckpointingConfig

        cfg = deepspeed_config
        if not isinstance(cfg, dict):
            with open(cfg) as f:
                cfg = json.load(f)
        ac = ActivationCheckpointingConfig(
            **cfg.get("activation_checkpointing", {}))
        if checkpoint_in_cpu is None:
            checkpoint_in_cpu = ac.cpu_checkpointing
        if profile is None:
            profile = ac.profile
        if num_checkpoints is None:
            num_checkpoints = ac.number_checkpoints
    # reference semantics: each knob overwrites only when explicitly given
    # (checkpointing.py:825 docstring) — repeated configure() calls refine,
    # never silently reset
    _config["configured"] = True
    if mpu_ is not None:
        _config["mpu"] = mpu_
    if num_checkpoints is not None:
        _config["num_checkpoints"] = num_checkpoints
    if profile is not None:
        _config["profile"] = bool(profile)
    if checkpoint_in_cpu is not None:
        _config["policy"] = ("offload_dots_no_batch" if checkpoint_in_cpu
                             else "nothing")


def is_configured() -> bool:
    return _config["configured"]


def reset() -> None:
    _config.update(configured=False, policy="nothing", profile=False,
                   num_checkpoints=None, mpu=None, seed=None)


def checkpoint(function, *args) -> Any:
    """Drop-in for the reference ``checkpoint`` (:743): run ``function`` now,
    drop its internal activations, recompute them during backward."""
    fn = jax.checkpoint(function,
                        policy=resolve_remat_policy(_config["policy"]))
    if not _config["profile"]:
        return fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    log_dist(f"checkpointing: forward(enqueue) "
             f"{(time.perf_counter() - t0) * 1e3:.2f} ms", ranks=[0])
    return out


# -- RNG tracker parity (Megatron integrations import these) ---------------

def model_parallel_cuda_manual_seed(seed: int) -> None:
    """Parity shim: store the seed (also registered in the tracker under
    'model-parallel-rng', as the reference does). Under SPMD all devices run
    one program; per-call randomness comes from explicit jax.random keys, so
    there is no per-device RNG state to fork the way torch model parallelism
    needs."""
    _config["seed"] = int(seed)
    _CUDA_RNG_STATE_TRACKER.add("model-parallel-rng", seed)


def get_rng_state(*_, **__):
    return {"seed": _config["seed"]}


def model_parallel_reconfigure_tp_seed(seed: int) -> None:
    model_parallel_cuda_manual_seed(seed)


class CudaRNGStatesTracker:
    """Minimal tracker parity (reference ``CudaRNGStatesTracker``): stores
    named seeds; ``fork`` is a no-op context (explicit keys make forked
    device RNG state unnecessary)."""

    def __init__(self):
        self.states = {}

    def reset(self):
        self.states = {}

    def add(self, name, seed):
        self.states[name] = int(seed)

    def get_states(self):
        return dict(self.states)

    def set_states(self, states):
        self.states = dict(states)

    def fork(self, name="model-parallel-rng"):
        import contextlib

        return contextlib.nullcontext()


_CUDA_RNG_STATE_TRACKER = CudaRNGStatesTracker()


def get_cuda_rng_tracker() -> CudaRNGStatesTracker:
    return _CUDA_RNG_STATE_TRACKER
