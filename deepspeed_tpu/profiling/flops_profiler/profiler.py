"""Flops profiler: per-module flops/MACs/params for any jittable function.

Counterpart of ``deepspeed/profiling/flops_profiler/profiler.py:17``
(``FlopsProfiler``), which monkey-patches ``torch.nn.functional`` to count
flops as modules execute. The TPU-native mechanism is better-grounded: trace
the function once to a jaxpr and WALK THE GRAPH, computing flops per
primitive (dot_general/conv from dimension numbers, elementwise from output
sizes) and attributing each equation to its originating flax module via the
JAX name stack (the same metadata XLA shows in HLO). ``lax.scan`` bodies are
counted once and multiplied by trip count, so a scanned N-layer model costs
one layer's analysis.

The same walk (``walk_jaxpr``) is the train step's own count of its matrix
work (``monitor/perf.py StepCost``): products alone, by the innermost ``ds.``
scope and by phase, a ``shard_map`` body times its devices, a ``cond``'s
cheapest branch, a Pallas call by the table ``ops/pallas MATMUL_FLOPS``.

No execution, no monkey-patching, exact shapes — and it works on anything
jittable, not just ``nn.Module``s.
"""

import collections
import dataclasses
import re
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax import core as jcore

# primitives whose flops = number of output elements (one VPU op per element)
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "neg", "sign",
    "floor", "ceil", "round", "abs", "exp", "log", "log1p", "expm1", "tanh",
    "sin", "cos", "tan", "logistic", "rsqrt", "sqrt", "cbrt", "erf", "erfc",
    "erf_inv", "and", "or", "xor", "not", "select_n", "clamp", "nextafter",
    "atan2", "square", "integer_pow",
}
# comparison / cheap ops counted as 1 flop per output element as well
_ELEMENTWISE |= {"eq", "ne", "lt", "le", "gt", "ge", "is_finite"}
# reductions: flops = number of INPUT elements (one accumulate per element)
_REDUCTIONS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
               "reduce_and", "reduce_or", "argmax", "argmin",
               "cumsum", "cummax", "cummin", "cumprod", "cumlogsumexp"}
# zero-flop data movement
_ZERO = {"broadcast_in_dim", "reshape", "transpose", "slice", "dynamic_slice",
         "dynamic_update_slice", "concatenate", "pad", "rev", "gather",
         "scatter", "scatter-add", "squeeze", "convert_element_type",
         "bitcast_convert_type", "iota", "copy", "stop_gradient", "select_and_scatter_add",
         "reduce_precision", "real", "imag", "split", "expand_dims"}


def _size(aval) -> int:
    try:
        return int(np.prod(aval.shape)) if aval.shape else 1
    except Exception:
        return 0


def _product_macs(lhs, rhs, dimension_numbers, rhs_group=()) -> int:
    """batch x M x N x K of a product from its dimension numbers; ``rhs``'s
    ``rhs_group`` dimensions (a grouped product's) are no part of N."""
    (lc, rc), (lb, rb) = dimension_numbers
    size = lambda shape, skip: int(np.prod(
        [s for i, s in enumerate(shape) if i not in skip]) or 1)
    batch = int(np.prod([lhs.shape[i] for i in lb])) if lb else 1
    contract = int(np.prod([lhs.shape[i] for i in lc])) if lc else 1
    return batch * contract * size(lhs.shape, tuple(lc) + tuple(lb)) \
        * size(rhs.shape, tuple(rc) + tuple(rb) + tuple(rhs_group))


def _dot_general_flops(eqn) -> Tuple[int, int]:
    """(flops, macs) from dimension numbers: 2 * batch * M * N * K."""
    macs = _product_macs(eqn.invars[0].aval, eqn.invars[1].aval,
                         eqn.params["dimension_numbers"])
    return 2 * macs, macs


def _conv_flops(eqn) -> Tuple[int, int]:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    fgc = eqn.params.get("feature_group_count", 1)
    # per output element: one MAC per (input-channel/groups x kernel-spatial)
    dn = eqn.params["dimension_numbers"]
    k_spatial = int(np.prod([rhs.shape[i] for i in dn.rhs_spec[2:]])) \
        if hasattr(dn, "rhs_spec") else int(np.prod(rhs.shape[2:]))
    # the kernel's in-channel dim is ALREADY in_features/feature_group_count;
    # do not divide by fgc again
    cin = rhs.shape[dn.rhs_spec[1]] if hasattr(dn, "rhs_spec") else rhs.shape[1]
    macs = _size(out) * cin * k_spatial
    return 2 * macs, macs


def _ragged_dot_flops(eqn) -> Tuple[int, int]:
    """A grouped product as the dense one over its operands: ``lhs [M, K] x
    rhs [G, K, N]`` is ``2 M K N`` (a row meets ONE group's weight), and so
    is the transposed form ``lhs [M, K] x rhs [M, N] -> [G, K, N]`` (ragged
    along the contraction) -- read off the dimension numbers, ``rhs``'s
    group dimensions left out."""
    numbers = eqn.params.get("ragged_dot_dimension_numbers")
    dims, group = ((((1,), (1,)), ((), ())), (0,)) if numbers is None else \
        (numbers.dot_dimension_numbers, numbers.rhs_group_dimensions)
    macs = _product_macs(eqn.invars[0].aval, eqn.invars[1].aval, dims, group)
    return 2 * macs, macs


#: the innermost ``ds.`` scope of a name-stack path: the rule
#: ``benchmark/scope_reduce.py SCOPE`` applies to a device operation's
#: ``op_name`` (the same expression; a test holds the two together)
SCOPE = re.compile(r"ds\.[a-z_0-9]+")
UNSCOPED = "(unscoped)"
PHASES = ("forward", "backward", "replayed")


def phase_of(path: str) -> str:
    """forward / backward / replayed from the marks the transformations leave
    in a name stack, as ``scope_reduce.phase_of`` reads them off ``op_name``:
    what ``jax.checkpoint``'s backward runs again stands under
    ``rematted_computation``, the backward pass under ``transpose(``."""
    if "rematted_computation" in path:
        return "replayed"
    return "backward" if "transpose(" in path else "forward"


@dataclasses.dataclass
class ModuleProfile:
    """One node of the per-module profile tree."""

    name: str
    flops: int = 0
    macs: int = 0
    children: Dict[str, "ModuleProfile"] = dataclasses.field(default_factory=dict)

    def child(self, name: str) -> "ModuleProfile":
        if name not in self.children:
            self.children[name] = ModuleProfile(name)
        return self.children[name]

    def total_flops(self) -> int:
        return self.flops + sum(c.total_flops() for c in self.children.values())

    def total_macs(self) -> int:
        return self.macs + sum(c.total_macs() for c in self.children.values())

    def add(self, other: "ModuleProfile") -> None:
        self.flops += other.flops
        self.macs += other.macs
        for name, node in other.children.items():
            self.child(name).add(node)


@dataclasses.dataclass
class Walk:
    """What one walk of a jaxpr counted. ``tree``: every counted operation
    under its name stack (the printed profile). ``scopes``: the MATRIX
    operations alone (``2 x`` the multiply-accumulates of ``dot_general``,
    convolutions, grouped products and the Pallas kernels of
    ``ops/pallas MATMUL_FLOPS``), by the innermost ``ds.`` scope of the path
    and by phase. Every count is GLOBAL: a ``shard_map`` body counts once a
    device of its mesh axes. A ``cond`` counts its branch of the FEWEST
    matrix operations (a lower bound) and keeps the distance to its largest
    as ``cond_spread_flops``; what counts nothing -- a ``while``, whose trip
    count is data, a Pallas kernel without an entry or with a traced grid --
    stands in ``uncounted`` by name with its calls."""

    tree: ModuleProfile = dataclasses.field(
        default_factory=lambda: ModuleProfile("total"))
    scopes: Dict[str, Dict[str, int]] = dataclasses.field(default_factory=dict)
    cond_spread_flops: int = 0
    uncounted: Dict[str, int] = dataclasses.field(
        default_factory=collections.Counter)

    def _row(self, scope: str) -> Dict[str, int]:
        return self.scopes.setdefault(scope, dict.fromkeys(PHASES, 0))

    def matmul_flops(self, *phases: str) -> int:
        return sum(v for row in self.scopes.values() for k, v in row.items()
                   if not phases or k in phases)

    def count(self, stack: Tuple[str, ...], flops: int, macs: int) -> None:
        node = self.tree
        for part in stack:
            node = node.child(part)
        node.flops += flops
        node.macs += macs
        if macs:
            path = "/".join(stack)
            found = SCOPE.findall(path)
            self._row(found[-1] if found else UNSCOPED)[
                phase_of(path)] += 2 * macs

    def add(self, other: "Walk") -> None:
        self.tree.add(other.tree)
        for scope, row in other.scopes.items():
            mine = self._row(scope)
            for phase, flops in row.items():
                mine[phase] += flops
        self.cond_spread_flops += other.cond_spread_flops
        self.uncounted.update(other.uncounted)       # a Counter: adds


def _shard_map_devices(eqn) -> int:
    """The devices a ``shard_map`` body runs on: the product of the mesh
    axes it is manual over (all of them where it names none)."""
    mesh = eqn.params["mesh"]
    axes = eqn.params.get("manual_axes") or mesh.axis_names
    return int(np.prod([mesh.shape[a] for a in axes]))


def _pallas_flops(eqn) -> Optional[int]:
    """The matrix operations a ``pallas_call`` RUNS, from the table beside
    the kernels' names -- the kernel's body is never entered: its jaxpr is
    ONE grid step's, over blocks. None: no entry, or one that cannot count
    this call (a grid with a traced bound)."""
    from ...ops.pallas import MATMUL_FLOPS

    count = MATMUL_FLOPS.get(eqn.params.get("name"))
    if count is None:
        return None
    mapping = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(b, "block_size", b) for b in m.block_shape)
              for m in mapping.block_mappings]
    # behind the grid's traced bounds and the scalar-prefetch arguments
    operands = [v.aval.shape for v in eqn.invars[-mapping.num_inputs:]]
    return count(operands, [v.aval.shape for v in eqn.outvars],
                 tuple(mapping.grid), blocks)


def _sub_jaxprs(eqn) -> List[Tuple[Any, int]]:
    """(inner jaxpr, multiplier) pairs of an equation that holds others: a
    ``scan``'s body times its length, a ``shard_map``'s times its devices."""
    name = eqn.primitive.name
    if name == "scan":
        return [(eqn.params["jaxpr"].jaxpr, int(eqn.params["length"]))]
    mult = _shard_map_devices(eqn) if name == "shard_map" else 1
    out = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            out.append((sub.jaxpr if hasattr(sub, "jaxpr") else sub, mult))
    return out


def _walk(jaxpr, walk: Walk, mult: int, prefix: Tuple[str, ...]) -> None:
    for eqn in jaxpr.eqns:
        stack = prefix + tuple(
            s for s in str(eqn.source_info.name_stack).split("/") if s)
        name = eqn.primitive.name
        if name == "while":
            walk.uncounted[name] += mult
            continue
        if name == "pallas_call":
            flops = _pallas_flops(eqn)
            if flops is None:
                walk.uncounted[eqn.params.get("name") or name] += mult
            else:
                walk.count(stack, flops * mult, flops // 2 * mult)
            continue
        if name == "cond":
            branches = []
            for branch in eqn.params["branches"]:
                branches.append(Walk())
                _walk(branch.jaxpr, branches[-1], mult, stack)
            branches.sort(key=lambda b: b.matmul_flops())
            walk.add(branches[0])
            walk.cond_spread_flops += branches[-1].matmul_flops() \
                - branches[0].matmul_flops()
            continue
        subs = _sub_jaxprs(eqn)
        if subs:
            for sub, m in subs:
                _walk(sub, walk, mult * m, stack)
            continue
        if name == "dot_general":
            flops, macs = _dot_general_flops(eqn)
        elif name in ("ragged_dot", "ragged_dot_general"):
            flops, macs = _ragged_dot_flops(eqn)
        elif name == "conv_general_dilated":
            flops, macs = _conv_flops(eqn)
        elif name in _ELEMENTWISE:
            flops, macs = sum(_size(v.aval) for v in eqn.outvars), 0
        elif name in _REDUCTIONS:
            flops, macs = sum(_size(v.aval) for v in eqn.invars), 0
        else:
            continue
        walk.count(stack, flops * mult, macs * mult)


def walk_jaxpr(jaxpr) -> Walk:
    """Walk a (closed) jaxpr: the per-module tree and the matrix operations
    by scope. Needs no executable and runs nothing."""
    walk = Walk()
    _walk(getattr(jaxpr, "jaxpr", jaxpr), walk, 1, ())
    return walk


def profile_fn(fn: Callable, *args, **kwargs) -> ModuleProfile:
    """Trace ``fn(*args, **kwargs)`` and return the per-module flops tree.

    Works on any jittable callable; module attribution follows the JAX name
    stack (flax modules populate it automatically)."""
    return walk_jaxpr(jax.make_jaxpr(fn)(*args, **kwargs)).tree


def params_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
               if hasattr(p, "shape"))


def _flops_repr(n: float) -> str:
    for unit, scale in [("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)]:
        if abs(n) >= scale:
            return f"{n / scale:.2f} {unit}FLOPs"
    return f"{n:.0f} FLOPs"


class FlopsProfiler:
    """Engine-facing profiler (reference ``FlopsProfiler`` ``profiler.py:17``:
    start/stop/print around one training step).

    Usage mirrors the reference::

        prof = FlopsProfiler(engine)
        tree = prof.profile_step(batch)     # analytic graph walk
        prof.print_model_profile()

    The engine calls this automatically at ``flops_profiler.profile_step``
    when the config block is enabled (reference ``engine.py:1615``).
    """

    def __init__(self, engine=None, config=None):
        self.engine = engine
        self.config = config or (engine._config.flops_profiler if engine else None)
        self.tree: Optional[ModuleProfile] = None
        self.n_params: int = params_count(engine.state.params) if engine else 0
        self.step_time_s: Optional[float] = None

    def profile_step(self, shaped_batch, rng=None) -> ModuleProfile:
        """Analytically profile the engine's FULL train step (fwd+bwd+
        optimizer) — a pure trace, no device execution. The engine sets
        ``step_time_s`` from its own timed step for achieved-TFLOPs output."""
        eng = self.engine
        self.tree = profile_fn(eng._train_step_fn, eng.state, shaped_batch,
                               rng if rng is not None else jax.random.PRNGKey(0))
        return self.tree

    # -- reference-parity accessors (profiler.py get_total_*) --------------
    def get_total_flops(self) -> int:
        return self.tree.total_flops() if self.tree else 0

    def get_total_macs(self) -> int:
        return self.tree.total_macs() if self.tree else 0

    def get_total_params(self) -> int:
        return self.n_params

    def print_model_profile(self, module_depth: int = -1, top_modules: int = 1,
                            file=None):
        """Reference ``print_model_profile``: tree print with per-module flops
        and share of total."""
        if self.tree is None:
            raise RuntimeError("no profile captured yet - call profile_step() "
                               "(or profile_fn) before print_model_profile()")
        out = file or sys.stdout
        total = max(self.get_total_flops(), 1)
        print(f"params: {self.n_params:,}", file=out)
        print(f"total flops (analytic): {_flops_repr(total)}", file=out)
        if self.step_time_s:
            print(f"measured step: {self.step_time_s * 1e3:.1f} ms -> "
                  f"{total / self.step_time_s / 1e12:.1f} achieved TFLOPs",
                  file=out)

        def rec(node: ModuleProfile, depth, indent):
            if module_depth >= 0 and depth > module_depth:
                return
            kids = sorted(node.children.values(), key=lambda c: -c.total_flops())
            if depth > 0:
                tf = node.total_flops()
                print(f"{indent}{node.name}: {_flops_repr(tf)} "
                      f"({100.0 * tf / total:.1f}%)", file=out)
            shown = kids if depth == 0 else kids[:max(top_modules, 1)] \
                if top_modules > 0 else kids
            for c in shown:
                rec(c, depth + 1, indent + "  ")

        rec(self.tree, 0, "")


def get_model_profile(model, input_shape=None, args=None, kwargs=None,
                      params=None, rngs=None) -> Tuple[int, int, int]:
    """Reference ``get_model_profile``: (flops, macs, params) for one forward
    of a flax module. ``input_shape`` builds an int32 dummy batch (LM usage);
    or pass explicit ``args``/``kwargs``."""
    import jax.numpy as jnp

    if args is None:
        if input_shape is None:
            raise ValueError("need input_shape or args")
        args = (jnp.ones(input_shape, jnp.int32),)
    kwargs = kwargs or {}
    if params is None:
        params = model.init(rngs or jax.random.PRNGKey(0), *args, **kwargs)
        params = params.get("params", params)
    tree = profile_fn(
        lambda p, *a: model.apply({"params": p}, *a, **kwargs), params, *args)
    return tree.total_flops(), tree.total_macs(), params_count(params)
