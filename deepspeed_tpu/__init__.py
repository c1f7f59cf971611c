"""deepspeed_tpu — a TPU-native training & inference framework with the
capability surface of DeepSpeed (reference v0.7.3), built on JAX/XLA/Pallas.

Public API parity with ``deepspeed/__init__.py``: ``initialize`` (:51),
``init_inference`` (:225), ``add_config_arguments`` (:209), plus the module
namespaces (``comm``, ``zero``, ``moe``, ``ops``...).
"""

import time as _time

T_IMPORT = _time.perf_counter()

from .version import __version__  # noqa: E402,F401

from . import comm  # noqa: E402,F401
from . import parallel  # noqa: E402,F401
from .utils.logging import log_dist, logger  # noqa: E402,F401

#: seconds of the imports on the way to a training engine: this package's
#: root import (jax's own where nothing imported it before; it began at
#: ``T_IMPORT`` on the ``perf_counter`` clock) and, on the first
#: ``initialize``, the lazy ``runtime.engine`` import — the engine's set-up
#: record reports their sum as ``import_s``
IMPORT_SECONDS = {"package": _time.perf_counter() - T_IMPORT}


def initialize(*args, **kwargs):
    """Build a training engine. See ``deepspeed_tpu.runtime.engine``.

    Reference: ``deepspeed/__init__.py:51`` — returns
    ``(engine, optimizer, dataloader, lr_scheduler)``.
    """
    t0 = _time.perf_counter()
    from .runtime.engine import initialize as _initialize

    IMPORT_SECONDS.setdefault("engine", _time.perf_counter() - t0)
    return _initialize(*args, **kwargs)


def init_inference(*args, **kwargs):
    """Build an inference engine. Reference: ``deepspeed/__init__.py:225``."""
    from .inference.engine import init_inference as _init_inference

    return _init_inference(*args, **kwargs)


def add_config_arguments(parser):
    """Reference: ``deepspeed/__init__.py:209``."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for argument parsing)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS


#: reference-parity shortcut (``deepspeed.init_distributed``)
init_distributed = comm.init_distributed


_LAZY_MODULES = {"zero": ".runtime.zero", "moe": ".moe", "ops": ".ops",
                 "pipe": ".pipe", "module_inject": ".module_inject",
                 "checkpointing": ".checkpointing"}
_LAZY_NAMES = {
    "DeepSpeedEngine": (".runtime.engine", "DeepSpeedEngine"),
    "PipelineEngine": (".pipe.engine", "PipelineEngine"),
    "PipelineModule": (".pipe.module", "PipelineModule"),
    "DeepSpeedConfig": (".runtime.config", "DeepSpeedConfig"),
    "InferenceEngine": (".inference.engine", "InferenceEngine"),
    "ServingEngine": (".inference.serving", "ServingEngine"),
    "ServingConfig": (".inference.serving", "ServingConfig"),
    "init_serving": (".inference.serving", "init_serving"),
    "RejectedError": (".inference.serving", "RejectedError"),
}


def __getattr__(name):
    """Lazy module/class namespaces matching ``deepspeed.*`` (kept lazy so
    ``import deepspeed_tpu`` stays cheap and backend-neutral). Uses
    importlib (not ``from . import x``, whose fromlist check re-enters this
    __getattr__ and recurses)."""
    import importlib

    if name in _LAZY_MODULES:
        mod = importlib.import_module(_LAZY_MODULES[name], __name__)
        globals()[name] = mod
        return mod
    if name in _LAZY_NAMES:
        modname, attr = _LAZY_NAMES[name]
        val = getattr(importlib.import_module(modname, __name__), attr)
        globals()[name] = val
        return val
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY_MODULES) | set(_LAZY_NAMES))
