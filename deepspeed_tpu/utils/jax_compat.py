"""Process-level jax set-up shared by the entry points (tests, CLIs, benches,
``chip_smoke.py``): which platform the process runs on, where its
persistent compilation cache lives and what of the source is part of that
cache's key. Library code never calls these.
"""

import os

#: the checkout this package was imported from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_cpu_devices(n=8) -> None:
    """Run this process on the CPU platform with ``n`` virtual devices
    (``n=None``: switch the platform only). Call before the backend
    initializes — the ``--cpu`` rehearsal switch of the CLIs and the test
    harness."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    if n is not None:
        jax.config.update("jax_num_cpu_devices", n)


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache, say what of the source is
    part of a compiled program's key, and return the cache's directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it itself, and
    whoever set it knows where caches survive); otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the path
    is part of the cache key, and inside the checkout, because that is the
    one directory a chip call brings along.

    An operation's location holds ONE frame, its own line
    (``jax_traceback_in_locations_limit`` 1). A Pallas call's Mosaic payload
    is part of the key and carries its operations' locations: with jax's
    default each holds file, line and column of up to ten frames that reach
    the kernel, so the key would follow the layout of files that do not
    define the kernel -- a blank line above ``LlamaAttention`` re-keyed every
    flash cell. The kernel's own file stays in the key. Not
    ``jax_include_full_tracebacks_in_locations``: switched off it also drops
    the name stack from XLA's ``op_name``, by which a trace names every scope
    and kernel. Held by ``tests/unit/ops/test_tpu_compile.py
    test_a_moved_caller_line_keeps_the_lowered_kernel``."""
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 1)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
