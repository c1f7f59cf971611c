"""Attention whose visible keys a learned indexer chooses (DeepSeek-V3.2-Exp's
sparse attention, as Keye-VL-2.0's ``sa_config`` carries it), for training.

For a layer's normed input ``h [T, hidden]`` the indexer computes
``qI = RoPE(W_qI h) [T, J, d]``, ``kI = RoPE(LayerNorm(W_kI h)) [T, d]``
(one key shared by its ``J`` heads), ``w = (W_w h) (J d)^-1/2 [T, J]`` and
the index scores ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` in
float32. Query ``t`` attends to ``S_t``, the ``min(topk, t + 1)`` keys
``s <= t`` of largest ``I[t, s]``, a tie going to the lower index
(``lax.top_k``'s order), one set for all heads. The selection is EXACT and
by value: the k-th largest of a row is found by bisection over the ordered
bit pattern of its scores (32 counting passes, no sort), then ``I > v``
plus the first ``k - count(I > v)`` entries equal to ``v`` in index order
(14 more passes at 16k keys) — ``ReLU`` makes exact zeros common, so ties
are no corner case.

The indexer learns from the attention it gates (the published sparse
stage): ``KL(p^_t || softmax_{S_t} I[t, .])`` with ``p^_t`` the head-mean of
the attention probabilities over ``S_t``; ``p^`` and the indexer's input are
detached, so the model's weights take the language-model loss's gradient
only and the indexer's the KL term's only (the selection passes none).

Everything here works on ``[B, T, ...]`` in blocks of query rows so that
16k positions fit: the XLA paths never hold more than ``[heads, block, T]``
scores. ``attention_impl="flash"`` takes the index scores and their gradient
from the kernels of ``ops/pallas/sa_index.py`` (the causal tiles only, a tile
of products at a time), runs the core through the flash kernels under the
mask (``ops/pallas/flash_attention.py``) and takes the KL term and the
scores' gradient from the two kernels of ``ops/pallas/sa_probs.py``, which
rebuild ``p^`` a tile at a time from the saved log-sum-exp and reduce it
against the scores there: no ``[T, T]`` pass of the loss runs in XLA.
``masked_attention_xla`` and ``index_loss`` are the XLA path, and what those
kernels are tested against.
"""

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas import SA_MASK
from .layers import apply_rotary, model_dense

_INT_MIN = np.iinfo(np.int32).min


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """Keye-VL-2.0's ``sa_config``, key for key. ``q_chunk_size`` /
    ``kv_chunk_size`` are read as tile sizes (the rows a block of the XLA
    paths holds, and on those paths the tiles ``sa_kept_tile_share`` counts;
    under ``attention_impl="flash"`` it counts the kernels' own
    ``flash_block_q x flash_block_k`` tiles); the selection is per token."""
    indexer_head_dim: int = 64
    indexer_num_heads: int = 16
    indexer_num_kv_heads: int = 1
    kv_chunk_size: int = 512
    q_chunk_size: int = 512
    topk: int = 2048

    def __post_init__(self):
        if self.indexer_num_kv_heads != 1:
            raise NotImplementedError("the indexer's heads share one key")


class Indexer(nn.Module):
    """``(qI [B, T, J, d], kI [B, T, d], w [B, T, J] float32)`` of the
    normed input ``x``. ``cos`` / ``sin`` are the attention's tables
    ``[B, T, D/2]``: rotary over all ``d`` indexer columns at the same base
    reads every ``D/d``-th frequency of them."""

    config: object

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg, sa = self.config, self.config.sa_config
        B, T, _ = x.shape
        J, d = sa.indexer_num_heads, sa.indexer_head_dim
        step = cfg.head_dim // d
        cos, sin = cos[..., ::step], sin[..., ::step]
        qi = model_dense(cfg, J * d, "wq")(x).reshape(B, T, J, d)
        ki = model_dense(cfg, d, "wk")(x)
        ki = nn.LayerNorm(epsilon=1e-6, name="k_norm", dtype=jnp.float32,
                          param_dtype=jnp.float32)(ki).astype(x.dtype)
        w = model_dense(cfg, J, "weights_proj")(x).astype(jnp.float32) \
            * (J * d) ** -0.5
        return (apply_rotary(qi, cos, sin),
                apply_rotary(ki[:, :, None], cos, sin)[:, :, 0], w)


def _block(T, want):
    """Rows of one block: ``want`` where it divides ``T``."""
    return math.gcd(T, want)


def _by_rows(fn, block, *rows):
    """``fn`` over blocks of ``block`` rows of ``rows`` (arrays
    ``[B, T, ...]``), one at a time: the outputs ``[B, T, ...]``. Each block
    is a ``jax.checkpoint``: its backward pass holds one block's scores."""
    B, T = rows[0].shape[:2]
    n = T // block
    split = lambda a: jnp.moveaxis(
        a.reshape(B, n, block, *a.shape[2:]), 1, 0)
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(B, T, *a.shape[3:])
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)),
                      (jnp.arange(n) * block, *map(split, rows)))
    return jax.tree_util.tree_map(join, out)


def index_scores(qi, ki, w, block=512):
    """``I [B, T, T]`` float32 (every pair; the selection applies the
    causal rule). The products accumulate in float32; the weighted sum over
    the indexer's heads is element-wise float32, never a matrix product."""
    def rows(_, q, w):
        pre = jnp.einsum("bqjd,bkd->bjqk", q, ki,
                         preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(pre)
                       * jnp.swapaxes(w, 1, 2)[..., None], axis=1)

    return _by_rows(rows, _block(qi.shape[1], block), qi, w)


def _ordered_key(x):
    """float32 -> int32 of the same order; both zeros are one key, as they
    are one value to ``lax.top_k``."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(x == 0, 0, bits ^ ((bits >> 31) & 0x7FFFFFFF))


def _select_rows(row0, scores, topk):
    """The selection of ``scores [B, R, T]``, the rows ``row0 ..`` of the
    index scores: bool ``[B, R, T]``."""
    _, R, T = scores.shape
    t = row0 + jnp.arange(R, dtype=jnp.int32)[None, :, None]
    s = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    causal = s <= t
    key = jnp.where(causal, _ordered_key(scores), _INT_MIN)
    k_row = jnp.minimum(topk, t + 1)                     # [1, R, 1]
    count = lambda hit: jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)

    # the k-th largest key of each row, bit by bit from the sign down: the
    # largest v with count(key >= v) >= k
    def value_bit(i, v):
        cand = v | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= k_row, cand, v)

    v = jnp.where(count(key >= 0) >= k_row, 0, _INT_MIN)
    v = jax.lax.fori_loop(0, 31, value_bit, v)
    above, ties = key > v, key == v
    need = k_row - count(above)                          # >= 1 ties to take

    # the index of the need-th tie: the largest m with fewer than `need`
    # ties before it
    bits = max(1, (T - 1).bit_length())

    def index_bit(i, m):
        cand = m | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count(ties & (s < cand)) < need, cand, m)

    m = jax.lax.fori_loop(0, bits, index_bit, jnp.zeros_like(v))
    return (above | (ties & (s <= m))) & causal


def select_mask(scores, topk, block=512):
    """int8 ``[B, T, T]``: 1 where query ``t`` attends to key ``s``. Named
    for the remat policies bit-packed (``ds_sa_mask``, 32 MiB at 16k), so a
    replay neither runs the counting passes again nor can choose another
    set than the forward pass did."""
    T = scores.shape[1]
    mask = _by_rows(lambda row0, sc: _select_rows(row0, sc, topk),
                    _block(T, block), jax.lax.stop_gradient(scores))
    packed = checkpoint_name(jnp.packbits(mask, axis=-1), SA_MASK)
    return jnp.unpackbits(packed, axis=-1, count=T).astype(jnp.int8)


def kept_tile_share(tiles, block_q=512, block_k=512):
    """Tiles of the causal triangle that hold at least one selected pair,
    over its tiles, from ``tiles [nq, nk]`` bool
    (``flash_attention.mask_tiles``): the share of the causal entries that the
    flash kernels' tile table keeps under this mask — the same array builds
    that table."""
    nq, nk = tiles.shape
    causal = np.arange(nk)[None] * block_k < (np.arange(nq)[:, None] + 1) \
        * block_q
    return jnp.sum(tiles, dtype=jnp.float32) / causal.sum()


def masked_attention_xla(q, k, v, mask, block=512):
    """``(out [B, T, H, D], p^ [B, T, T] float32)`` of queries ``q
    [B, T, H, D]`` over the keys ``mask`` leaves them, ``k`` / ``v
    [B, T, Hkv, D]``: float32 scores and softmax in blocks of query rows;
    ``p^`` is the mean of the heads' probabilities."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]

    def rows(_, q, m):
        q = q.reshape(B, -1, Hkv, H // Hkv, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                       preferred_element_type=jnp.float32) * D ** -0.5
        p = jax.nn.softmax(
            jnp.where((m != 0)[:, None, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(q.dtype), v)
        return out.reshape(B, -1, H, D), jnp.mean(p, axis=(1, 2))

    return _by_rows(rows, _block(T, block), q, mask)


def index_loss(p_hat, scores, mask):
    """``mean_t KL(p^_t || softmax_{S_t} I[t, .])`` in float32; a ``p^`` of
    exactly zero adds zero."""
    keep = mask != 0
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    p = jnp.where(keep, p_hat, 0.0)
    kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                       - jnp.where(keep, logq, 0.0)), 0.0),
                 axis=-1)
    return jnp.mean(kl)


def indexed_attention(cfg, x, q, k, v, cos, sin):
    """The core of ``LlamaAttention`` under ``cfg.sa_config``, called from
    its compact method (the indexer's parameters are that module's):
    ``(out [B, T, H, D], stats)``, ``stats`` this layer's float32 scalars
    ``sa_index_loss`` and, with ``cfg.report_expert_load``,
    ``sa_kept_tile_share``."""
    sa = cfg.sa_config
    block = sa.q_chunk_size
    with jax.named_scope("ds.sa_index"):
        qi, ki, w = Indexer(cfg, name="indexer")(
            jax.lax.stop_gradient(x), cos, sin)
        if cfg.attention_impl == "flash":
            from ..ops.pallas import sa_index

            # tiles above the diagonal stay unwritten: every reader below
            # applies the causal rule through a ``where``
            scores = sa_index.index_scores(
                qi, ki, w, block_q=cfg.flash_block_q,
                block_k=cfg.flash_block_k)
        else:
            scores = index_scores(qi, ki, w, block)
    from ..ops.pallas.flash_attention import flash_attention, mask_tiles

    flash = cfg.attention_impl == "flash"
    bq, bk = (cfg.flash_block_q, cfg.flash_block_k) if flash else \
        (sa.q_chunk_size, sa.kv_chunk_size)
    with jax.named_scope("ds.sa_select"):
        mask = select_mask(scores, sa.topk, block)
        # the flash kernels' tile tables and the counter read this one array
        tiles = mask_tiles(mask, bq, bk)
        stats = {"sa_kept_tile_share": kept_tile_share(tiles, bq, bk)} \
            if cfg.report_expert_load else {}
    if flash:
        from ..ops.pallas.sa_probs import index_kl
        from .layers import repeat_kv

        rep = q.shape[2] // k.shape[2]
        k, v = repeat_kv(k, rep), repeat_kv(v, rep)
        with jax.named_scope("ds.attention"):
            out, lse = flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk, mask=mask,
                tiles=tiles)
        with jax.named_scope("ds.sa_loss"):
            stats["sa_index_loss"] = index_kl(
                q, k, lse, scores, mask, block_q=bq, block_k=bk, tiles=tiles)
    else:
        with jax.named_scope("ds.attention"):
            out, p_hat = masked_attention_xla(q, k, v, mask, block)
        with jax.named_scope("ds.sa_loss"):
            stats["sa_index_loss"] = index_loss(
                jax.lax.stop_gradient(p_hat), scores, mask)
    return out, stats
