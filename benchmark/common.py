"""What every kind of run needs: files found by name, the model built from
its configuration file, the device's description, seeded weights."""

import dataclasses
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(path):
    """``"package.module:Name"`` -> the object."""
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def load_file_module(directory, name):
    """A module by FILE name (metric names contain dots), or None."""
    path = os.path.join(HERE, directory, f"{name}.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name}".replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def sizes_of(config, depth, tiny=False):
    """The configuration's sizes as run: its top-level keys, the tiny set
    laid over them in a CPU rehearsal, the depth the cell names."""
    sizes = {k: v for k, v in config.items()
             if isinstance(v, (int, float, bool)) or v is None}
    layers = config["num_hidden_layers"]
    if tiny:
        sizes.update({k: v for k, v in config["tiny"].items()
                      if not isinstance(v, dict)})
        layers = config["tiny"]["num_hidden_layers"]
    sizes["num_hidden_layers"] = layers[depth]
    sizes["head_dim"] = sizes["hidden_size"] // sizes["num_attention_heads"]
    return sizes


def build_model(config, sizes, **overrides):
    """(model config object, flax module) from the configuration file."""
    cls = resolve(config["model_config"])
    fields = {f.name for f in dataclasses.fields(cls)}
    cfg = cls(**{**{k: v for k, v in sizes.items() if k in fields},
                 **overrides})
    return cfg, resolve(config["model"])(cfg)


def cell_mesh(chips, **axes):
    """The cell's mesh: over all the machine's chips where it takes them all
    (``jax.make_mesh`` then lays the axes onto the ICI), else over the first
    ``chips`` of them (a one-chip cell on a larger machine, the tests)."""
    import jax

    from deepspeed_tpu.parallel import build_mesh

    if len(jax.devices()) == chips:
        return build_mesh(**axes)
    return build_mesh(devices=jax.devices()[:chips], **axes)


def device_info():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip; 0 where the backend reports
    none (CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return max(peaks) if peaks else 0


def peak_flops(device_kind):
    """bf16 FLOP/s of one chip; a device that is not in peaks.json is an
    error, not a default."""
    return load_json("peaks.json")[device_kind]["bf16_flops_per_s"]


def seeded_bf16_params(model, seed):
    """Random bf16 weights made on the device in ONE jitted call: uniform
    matrices of standard deviation 0.02, unit norm scales. "rbg" bits and a
    uniform draw: threefry and the normal's erf_inv each cost half a minute
    of chip time on 3.7e9 weights (PERF.md, PR 21)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    a = 0.02 * 3 ** 0.5

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = [jnp.ones(s.shape, jnp.bfloat16)
               if str(getattr(p[-1], "key", "")) == "scale"
               else jax.random.uniform(k, s.shape, jnp.bfloat16, -a, a)
               for (p, s), k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.key(seed % (2 ** 32), impl="rbg"))


def rel_l2(got, ref):
    """||got - ref|| / ||ref - mean(ref)||, rows being logit vectors: the
    error against the logits' own spread. It concentrates: a sum over tens
    of thousands of entries, not their largest."""
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    spread = ref - ref.mean(-1, keepdims=True)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(spread))
