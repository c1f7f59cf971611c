"""Ulysses-style sequence parallelism (all_to_all head↔sequence swap).

The 2022 reference has no sequence parallelism (SURVEY §2.3: closest levers
are block-sparse attention and activation partitioning,
``ops/sparse_attention/``, ``activation_checkpointing/checkpointing.py:367``);
this module delivers the modern DeepSpeed-Ulysses capability TPU-natively.

Mechanism: activations flow through the network sharded over the ``seq`` mesh
axis on the token dimension. Attention needs every query to see every key, so
around the attention core we RE-shard: tokens gather, heads scatter
(``[B, T/sp, H, D] → [B, T, H/sp, D]``), compute attention locally per head
group, and swap back. On GPU this is two explicit all_to_alls
(DeepSpeed-Ulysses' ``DistributedAttention``); on TPU it is two
``with_sharding_constraint`` calls — the XLA SPMD partitioner inserts the
all_to_alls, which ride ICI. Head count must divide the ``seq`` axis size.
"""

import jax

from jax.sharding import PartitionSpec as P

from ..parallel.topology import BATCH_AXES, get_mesh


def _axis_size(mesh, name: str) -> int:
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def ulysses_attention(q, k, v, causal: bool = False, bias=None,
                      attention_core=None, mesh=None):
    """Attention with Ulysses sequence-parallel resharding.

    q/k/v: logical ``[B, T, H, D]`` (token dim sharded over ``seq`` by the
    surrounding program). ``attention_core(q, k, v, bias, causal)`` defaults
    to the XLA softmax core; pass the flash kernel for long T.

    Head count must divide ``seq * model`` — like DeepSpeed-Ulysses, an
    indivisible head count is an error rather than a silent fallback to
    full-sequence attention (which would quietly reinstate the O(T²) memory
    SP was enabled to avoid; use ring attention for head-count-independent
    scaling).
    """
    mesh = mesh or get_mesh()
    sp = _axis_size(mesh, "seq")
    tp = _axis_size(mesh, "model")
    H = q.shape[2]
    if sp > 1 and H % (sp * tp) != 0:
        raise ValueError(
            f"Ulysses needs head count ({H}) divisible by seq*model axes "
            f"({sp}*{tp}); use attention_impl='ring' for this configuration")

    # Inside a partial-manual shard_map (the pipeline ring: pipe/data/expert
    # manual, seq/model auto) a sharding constraint may only name the AUTO
    # axes — the manual ones are already per-device. Dropping them keeps the
    # head<->seq reshard meaningful exactly where the partitioner acts.
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)

    def free(axes):
        kept = tuple(a for a in (axes if isinstance(axes, (tuple, list))
                                 else (axes,)) if a not in manual)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    if sp > 1:
        # heads take over the seq shard: tokens become fully local per shard
        head_spec = P(free(BATCH_AXES), None, free(("model", "seq")), None)
        q = jax.lax.with_sharding_constraint(q, jax.NamedSharding(mesh, head_spec))
        k = jax.lax.with_sharding_constraint(k, jax.NamedSharding(mesh, head_spec))
        v = jax.lax.with_sharding_constraint(v, jax.NamedSharding(mesh, head_spec))

    if attention_core is None:
        from ..models.layers import dot_product_attention

        out = dot_product_attention(q, k, v, bias=bias, causal=causal,
                                    attention_impl="xla")
    else:
        out = attention_core(q, k, v, bias, causal)

    if sp > 1:
        # back to token-sharded for the rest of the block
        out = jax.lax.with_sharding_constraint(
            out, jax.NamedSharding(
                mesh, P(free(BATCH_AXES), free("seq"), free("model"), None)))
    return out


def ulysses_flash_attention(q, k, v, causal: bool = True, mesh=None,
                            block_q: int = 512, block_k: int = 512,
                            window=None):
    """Ulysses with the FLASH kernel on each shard — the DeepSpeed-Ulysses
    execution shape for LONG sequences.

    The auto-sharding ``ulysses_attention`` leaves the attention core to the
    partitioner, which cannot partition a Pallas call; this variant makes
    the head<->token swap EXPLICIT inside a shard_map over ``seq``:
    ``lax.all_to_all`` turns the token shard ``[B, T/sp, H, D]`` into a head
    shard ``[B, T, H/sp, D]`` (two ICI all_to_alls, the wire pattern of
    DeepSpeed-Ulysses), the flash kernel runs on that LOCAL full-sequence /
    local-heads block (O(T * block) memory via online softmax), and the
    inverse all_to_all restores token sharding. Backward differentiates
    through (all_to_all transposes to itself on the reverse permutation).

    Divisibility: with tensor parallelism (``model`` axis = tp > 1, r4)
    heads split over TP first, so ``H % tp == 0`` and the PER-TP-SHARD
    head count must divide the ``seq`` axis (``(H // tp) % sp == 0``);
    without TP, plain ``H % sp == 0``.
    """
    from ..ops.pallas.flash_attention import flash_attention

    mesh = mesh or get_mesh()
    sp = _axis_size(mesh, "seq")
    if sp <= 1:
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, window=window)
    # TP composition (r4, lifting the r3 refusal): the Pallas call cannot be
    # partitioned over an AUTO model axis, so when tp > 1 the shard_map goes
    # manual over BOTH axes — heads shard explicitly over `model` (exact:
    # heads are independent), tokens over `seq`, and each (seq, model) shard
    # runs the kernel on its full-sequence / local-head block.
    tp = _axis_size(mesh, "model")
    H = q.shape[2]
    if tp > 1 and H % tp:
        raise ValueError(f"ulysses_flash needs head count ({H}) divisible "
                         f"by the model axis ({tp})")
    if (H // max(tp, 1)) % sp:
        raise ValueError(f"ulysses_flash needs per-TP-shard head count "
                         f"({H}//{tp}) divisible by the seq axis ({sp}); "
                         "use ring attention for head-count-independent "
                         "scaling")
    if q.shape[1] % sp:
        raise ValueError(f"sequence length {q.shape[1]} not divisible by "
                         f"seq axis size {sp}")

    def local(ql, kl, vl):
        # token shard -> head shard: split heads (axis 2), gather tokens
        # (axis 1) across the seq group
        swap = lambda x: jax.lax.all_to_all(x, "seq", split_axis=2,
                                            concat_axis=1, tiled=True)
        qh, kh, vh = swap(ql), swap(kl), swap(vl)
        # post-swap each shard holds the FULL sequence (local heads), so the
        # kernel's global sliding window applies unchanged
        out = flash_attention(qh, kh, vh, causal=causal, block_q=block_q,
                              block_k=block_k, window=window)
        # head shard -> token shard
        return jax.lax.all_to_all(out, "seq", split_axis=1, concat_axis=2,
                                  tiled=True)

    if tp > 1:
        spec = P(None, "seq", "model", None)
        manual = frozenset({"seq", "model"})
    else:
        spec = P(None, "seq")
        manual = frozenset({"seq"})
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, axis_names=manual, check_vma=False)
    if not any(isinstance(x, jax.core.Tracer) for x in (q, k, v)):
        return jax.jit(fn)(q, k, v)  # partial-manual needs a jit trace
    return fn(q, k, v)


class DistributedAttention:
    """Parity shim for DeepSpeed-Ulysses' ``DistributedAttention`` wrapper:
    wraps any attention core with the head↔seq swap."""

    def __init__(self, attention_core=None, mesh=None, scatter_idx: int = 2,
                 gather_idx: int = 1):
        # scatter/gather idx accepted for API parity; the sharding constraint
        # formulation fixes them at (heads=2, tokens=1)
        self.attention_core = attention_core
        self.mesh = mesh

    def __call__(self, q, k, v, causal: bool = False, bias=None):
        return ulysses_attention(q, k, v, causal=causal, bias=bias,
                                 attention_core=self.attention_core, mesh=self.mesh)
