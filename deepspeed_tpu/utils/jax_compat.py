"""Process-level jax set-up shared by the entry points (tests, CLIs, benches,
``chip_smoke.py``): which platform the process runs on and where its
persistent compilation cache lives. Library code never calls these.
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
    """Place jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it itself, and
    whoever set it knows where caches survive); otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the path
    is part of the cache key, and inside the checkout, because that is the
    one directory a chip call brings along."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
