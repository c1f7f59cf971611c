"""The Pallas kernels of the main path, and Mixtral's expert layer under
``expert=4``, must COMPILE for the chip.

Interpret mode (every other kernel test) runs the kernel's Python body and
says nothing about Mosaic's layout rules: before PR 21 every attention
kernel here passed its parity tests and was refused by the TPU compiler at
real widths (block-shape rule, unaligned dynamic sublane slices). The TPU
compiler is installed with jax and compiles for a chip that is described,
not attached (on-chip-measurement guide §2) — so each kernel is lowered and
compiled for a ``v5e:2x2`` topology at ``chip_smoke.py``'s widths (H32 /
Hkv8 / D128, bf16). Nothing runs: this guards layouts, not results.
"""

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
from deepspeed_tpu.ops.pallas.flash_attention import (BlockDiffusion,
                                                     flash_attention)
from deepspeed_tpu.ops.pallas.fused_adam import _run_leaf
from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul
from deepspeed_tpu.ops.pallas.ragged_attention import ragged_paged_attention

H, HKV, D = 32, 8, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    """A described ``v5e:2x2``; the persistent compile cache is off around
    the module (a TPU executable written here could never be read back
    without a chip, and the next run would warn about it)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Sharding on one described v5e chip."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_v5e(monkeypatch):
    """``mixtral._grouped_tiles`` asks the backend and the device kind, and
    ``flash_attention.fused_backward`` the device kind, which are the CPU's
    here: answer for the described chip."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    monkeypatch.setattr(gm, "backend", lambda: "tpu")
    monkeypatch.setattr(gm, "device_kind", lambda: "TPU v5 lite")


def _scale_kw(scales):
    return dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}


def _flash(bwd, window, shape=(1, 2048, H, D), v_dim=None):
    q = (shape, BF16)
    v = (shape[:3] + (v_dim or shape[3],), BF16)
    fwd = functools.partial(flash_attention, causal=True, interpret=False,
                            window=window)
    if not bwd:
        return fwd, [q, q, v]
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), [q, q, v]


def _flash_sa(bwd, T=16384):
    """keye-vl2-30b-a3b.train.16k: a [B, T, T] int8 mask that is data,
    shared by 32 heads of 128; the backward reads it too."""
    q = ((1, T, H, D), BF16)
    fwd = functools.partial(flash_attention, causal=True, interpret=False)
    args = [q, q, q, ((1, T, T), jnp.int8)]
    if not bwd:
        return (lambda q, k, v, m: fwd(q, k, v, mask=m)), args
    loss = lambda q, k, v, m: fwd(q, k, v, mask=m)[0].astype(
        jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), args


def _sa_probs(bwd, T=16384):
    """keye-vl2-30b-a3b.train.16k: the indexer's loss from the saved
    log-sum-exp and the ``[T, T]`` float32 scores, a tile of the head-mean
    probabilities in VMEM; the backward writes the scores' gradient onto a
    zeroed buffer."""
    from deepspeed_tpu.ops.pallas.sa_probs import index_kl

    q = ((1, T, H, D), BF16)
    fwd = functools.partial(index_kl, interpret=False)
    args = [q, q, ((1, H, T), jnp.float32), ((1, T, T), jnp.float32),
            ((1, T, T), jnp.int8)]
    return (jax.grad(fwd, argnums=3) if bwd else fwd), args


def _sa_index(bwd, T=16384):
    """keye-vl2-30b-a3b.train.16k: the indexer's 16 heads of 64 over one
    shared key, scores of every causal 512 x 512 tile; the backward's two
    kernels recompute the products from the same three inputs."""
    from deepspeed_tpu.ops.pallas.sa_index import index_scores

    args = [((1, T, 16, 64), BF16), ((1, T, 64), BF16),
            ((1, T, 16), jnp.float32)]
    fwd = functools.partial(index_scores, interpret=False)
    if not bwd:
        return fwd, args
    loss = lambda qi, ki, w: jnp.sum(jnp.tril(fwd(qi, ki, w)[0]))
    return jax.grad(loss, argnums=(0, 1, 2)), args


def _ssm_scan(bwd, T=8192, C=5120, N=16):
    """phi4-mini-flash.train.8k: one Mamba layer's selective scan, 5,120
    channels of 16 states over 8,192 positions, bf16 ``u`` beside float32
    ``delta``; the backward recomputes a chunk's states in VMEM."""
    from deepspeed_tpu.ops.pallas.selective_scan import selective_scan

    args = [((1, T, C), BF16), ((1, T, C), jnp.float32),
            ((C, N), jnp.float32), ((1, T, N), BF16), ((1, T, N), BF16),
            ((C,), jnp.float32)]
    fwd = functools.partial(selective_scan, interpret=False)
    if not bwd:
        return fwd, args
    return jax.grad(lambda *a: jnp.sum(fwd(*a)), argnums=tuple(range(6))), \
        args


def _gdn_rule(bwd, T=8192, heads=32, d=128, chunk=64):
    """qwen3-next-80b-a3b.train.8k: one delta-rule layer's chunked rule, 32
    value heads of 128 x 128 over 128 chunks of 64, bf16 q, k, v beside the
    float32 running decays, ``beta`` and chunk inverses XLA hands over; the
    backward rebuilds a chunk in VMEM from its saved boundary state."""
    from deepspeed_tpu.ops.pallas import gdn_rule

    seq = ((1, T, heads, d), BF16)
    row = ((1, heads, T // chunk, chunk), jnp.float32)
    args = [seq, seq, seq, row, row, (row[0] + (chunk,), jnp.float32)]
    fwd = functools.partial(gdn_rule.chunk_rule,
                            tiling=gdn_rule.plan("tpu", 1, d, d, chunk),
                            interpret=False)
    if not bwd:
        return fwd, args
    return jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
                    argnums=tuple(range(6))), args


def _gdn_mix(kernel, T=8192):
    """qwen3-next-80b-a3b.train.8k: what stands around the rule in one
    delta-rule layer, 16 key heads of 128 + 128 + 256 + 256 columns read in
    place from ``in_proj_qkvz``'s ``[1, 8192, 12288]`` output, four taps, at
    the tiling the chooser gives the cell; each of the four kernels alone,
    behind its jitted entry."""
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.ops.pallas import gdn_mix

    heads = gdn_mix.Heads(16, D, 2, D, 4)
    rows = gdn_mix.plan("tpu", 1, heads).rows
    premix = gdn_mix._Static(heads, rows, False, qn._conv_act,
                             qn._unit_length)
    gate = gdn_mix._Static(heads, rows, False, eps=1e-6)
    qkvz = ((1, T, 16 * 6 * D), BF16)
    head = ((1, T, 32 * D), BF16)
    taps, scale = ((4, 16 * 4 * D), jnp.float32), ((1, D), jnp.float32)
    return {
        "premix_fwd": (lambda *a: gdn_mix._premix_fwd(*a, premix),
                       [qkvz, taps]),
        "premix_bwd": (lambda *a: gdn_mix._premix_bwd(*a, premix),
                       [qkvz, taps, head, head, head, head]),
        "gate_fwd": (lambda *a: gdn_mix._gate_fwd(*a, gate),
                     [head, qkvz, scale]),
        "gate_bwd": (lambda *a: gdn_mix._gate_bwd(*a, gate),
                     [head, head, qkvz, scale]),
    }[kernel]


def _flash_key_mask():
    """Serving prefill (``layers.flash_prefill_from_empty``): forward only,
    kv heads un-repeated, a [B, Tk] key-padding mask."""
    B, T = 2, 2048
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    return (lambda q, k, v, mask: fn(q, k, v, key_mask=mask),
            [((B, T, H, D), BF16), ((B, T, HKV, D), BF16),
             ((B, T, HKV, D), BF16), ((B, T), jnp.int32)])


def _ragged(int8, window):
    T, N, bs, R, nb = 135, 2048, 16, 8, 64  # the smoke's mixed step
    pages = ((N, HKV, bs, D), jnp.int8 if int8 else BF16)
    row = ((R,), jnp.int32)
    args = [((T, H, D), BF16), pages, pages, ((R, nb), jnp.int32),
            row, row, row, row]
    if int8:
        args += [((N, HKV, bs), jnp.float32)] * 2

    def fn(q, k, v, bt, qs, ql, cs, cl, *scales):
        return ragged_paged_attention(q, k, v, bt, qs, ql, cs, cl,
                                      interpret=False, window=window,
                                      **_scale_kw(scales))

    return fn, args


def _decode(int8):
    B, S = 8, 4096
    cache = ((B, HKV, S, D), jnp.int8 if int8 else BF16)
    args = [((B, H, D), BF16), cache, cache, ((), jnp.int32)]
    if int8:
        args += [((B, HKV, S), jnp.float32)] * 2

    def fn(q, k, v, idx, *scales):
        return decode_attention(q, k, v, idx, interpret=False,
                                **_scale_kw(scales))

    return fn, args


def _quant_matmul(mode):
    K, N = 4096, 14336
    wq = ((K // 2, N), jnp.uint8) if mode == "int4" else ((K, N), jnp.int8)
    groups = K // 64 if mode == "int4" else 1
    fn = functools.partial(quant_matmul, mode=mode, interpret=False)
    return fn, [((32, K), BF16), wq, ((groups, N), jnp.float32)]


def _fused_adam():
    leaf = ((4096, 14336), jnp.float32)
    fn = functools.partial(_run_leaf, b1=0.9, b2=0.999, eps=1e-8,
                           weight_decay=0.1, adam_w_mode=True,
                           interpret=False)
    return fn, [leaf] * 4 + [((3,), jnp.float32)]


CASES = {
    "flash_fwd": lambda: _flash(False, None),
    "flash_fwd_window": lambda: _flash(False, 1024),
    "flash_fwd_bwd": lambda: _flash(True, None),
    "flash_fwd_bwd_window": lambda: _flash(True, 1024),
    # olmoe-1b-7b.train.4k: two 4096-token sequences, 16 heads (MHA)
    "flash_fwd_bwd_olmoe": lambda: _flash(True, None, (2, 4096, 16, D)),
    # mistral-7b.train.8k: one 8192-token sequence, the 4096 window binds
    "flash_fwd_bwd_train8k": lambda: _flash(True, 4096, (1, 8192, H, D)),
    # kimi-vl-a3b.train.8k: latent attention, queries and keys 128 + 64
    # wide, values 128 (Mosaic takes the 192-wide block as it is)
    "flash_fwd_bwd_mla_train8k": lambda: _flash(True, None, (1, 8192, 16, 192),
                                                v_dim=128),
    # zaya1-8b.train.8k: 8 query heads of 128 (keys and values repeated from
    # 2), plain causal
    "flash_fwd_bwd_cca_train8k": lambda: _flash(True, None, (1, 8192, 8, D)),
    # sdar-30b-a3b.train.8k: [x_t ; x_0], 2 x 8,192 rows under the block
    # rule; a cut tile's mask divides row numbers by the block length
    "flash_fwd_bwd_bd_train8k": lambda: _flash(
        True, BlockDiffusion(8192, 4), (1, 16384, H, D)),
    "flash_fwd_key_mask_gqa": _flash_key_mask,
    "flash_fwd_sa_train16k": lambda: _flash_sa(False),
    "flash_fwd_bwd_sa_train16k": lambda: _flash_sa(True),
    "sa_probs_train16k": lambda: _sa_probs(False),
    "sa_probs_bwd_train16k": lambda: _sa_probs(True),
    "sa_index_train16k": lambda: _sa_index(False),
    "sa_index_bwd_train16k": lambda: _sa_index(True),
    "ssm_scan_train8k": lambda: _ssm_scan(False),
    "ssm_scan_bwd_train8k": lambda: _ssm_scan(True),
    "gdn_rule_train8k": lambda: _gdn_rule(False),
    "gdn_rule_bwd_train8k": lambda: _gdn_rule(True),
    "gdn_premix_train8k": lambda: _gdn_mix("premix_fwd"),
    "gdn_premix_bwd_train8k": lambda: _gdn_mix("premix_bwd"),
    "gdn_gate_train8k": lambda: _gdn_mix("gate_fwd"),
    "gdn_gate_bwd_train8k": lambda: _gdn_mix("gate_bwd"),
    "ragged_bf16": lambda: _ragged(False, None),
    "ragged_bf16_window": lambda: _ragged(False, 4096),
    "ragged_int8": lambda: _ragged(True, None),
    "ragged_int8_window": lambda: _ragged(True, 4096),
    "decode_bf16": lambda: _decode(False),
    "decode_int8": lambda: _decode(True),
    "quant_matmul_int8": lambda: _quant_matmul("int8"),
    "quant_matmul_int4": lambda: _quant_matmul("int4"),
    "fused_adam_leaf": _fused_adam,
}


#: a flash call's backward in each flash cell, the longest dQ a v5e holds
#: and the first it does not
FLASH_BWD = {
    "olmoe_4k": lambda: _flash(True, None, (2, 4096, 16, D)),
    "train8k": lambda: _flash(True, 4096, (1, 8192, H, D)),
    "mla_train8k": lambda: _flash(True, None, (1, 8192, 16, 192), v_dim=128),
    "cca_train8k": lambda: _flash(True, None, (1, 8192, 8, D)),
    "sa_train16k": lambda: _flash_sa(True),
    # phi4-mini-flash.train.8k: a pair's two 64-wide heads, values side by
    # side; its 512 window and the full layers
    "da_window_train8k": lambda: _flash(True, 512, (1, 8192, 20, 64),
                                        v_dim=128),
    "da_train8k": lambda: _flash(True, None, (1, 8192, 20, 64), v_dim=128),
    # mellum2-12b-a2.5b.train.8k: three 1,024-window layers a period
    "swa_train8k": lambda: _flash(True, 1024, (1, 8192, H, D)),
    # sdar-30b-a3b.train.8k: 16,384 rows, 16 MiB resident
    "bd_train8k": lambda: _flash(True, BlockDiffusion(8192, 4),
                                 (1, 16384, H, D)),
    "longest_32k": lambda: _flash(True, None, (1, 32768, 2, D)),
    # 64 MiB resident, twice the share: the two kernels
    "over_the_share_64k": lambda: _flash(True, None, (1, 65536, 1, D)),
}


@pytest.mark.parametrize("name", sorted(FLASH_BWD))
def test_flash_backward_is_one_kernel_on_a_v5e(chip, on_v5e, name):
    """Where the rule answers for the described chip, every flash cell's
    backward compiles as ``ds_flash_bwd`` alone -- the head's float32 dQ
    ``[Tq, D]`` and its output block resident, 8 to 32 MiB, under the
    ``vmem_limit_bytes`` the call sets -- and a dQ over the share as the two
    kernels. (``test_kernel_compiles_for_v5e``'s ``flash_fwd_bwd*`` cases,
    which ask for this CPU's kind, keep compiling the two kernels at the
    cells' shapes, as a chip the rule does not know would.)"""
    fn, args = FLASH_BWD[name]()
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
              for shape, dtype in args]
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    called = re.findall(
        r"^\s*%?\w*(ds_flash_[a-z_]*[a-z])[_.\d]* = .*custom-call", hlo, re.M)
    backward = ["ds_flash_bwd_dkv", "ds_flash_bwd_dq"] \
        if name == "over_the_share_64k" else ["ds_flash_bwd"]
    assert sorted(called) == backward + ["ds_flash_fwd"]


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, args = CASES[name]()
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
              for shape, dtype in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the compiled program holds no Mosaic kernel"


#: one three-line caller of ``flash_attention``, as it stands and with blank
#: lines and indentation above and inside the call
_CALLER = """\
import jax.numpy as jnp
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
def loss(q, k, v):
    out = flash_attention(q, k, v, causal=True, interpret=False)
    return out.astype(jnp.float32).sum()
"""
_CALLER_MOVED = """\
import jax.numpy as jnp
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def loss(q, k, v):
    out = (

           flash_attention(q, k, v, causal=True, interpret=False))
    return out.astype(jnp.float32).sum()
"""


def _lowered_from(path, source, chip):
    """The lowered text, Mosaic payloads in, of the tiny flash gradient
    called from ``source`` as the module at ``path``."""
    path.write_text(source)
    module = {}
    exec(compile(source, str(path), "exec"), module)
    leaf = jax.ShapeDtypeStruct((1, 1024, 2, D), BF16, sharding=chip)
    return jax.jit(jax.grad(module["loss"], argnums=(0, 1, 2))).lower(
        leaf, leaf, leaf).as_text()


def test_a_moved_caller_line_keeps_the_lowered_kernel(chip, tmp_path):
    """Where the compile cache is configured, a kernel's Mosaic payload --
    part of the cache's key -- holds no frame of the code that calls it: one
    caller at other lines and columns of ONE path lowers to the same text,
    so a refactor's byte-for-byte proof survives a moved line. Under jax's
    own default the two differ, which is what the setting is for: should
    that half fail, jax changed what the setting means."""
    from deepspeed_tpu.utils.jax_compat import configure_compile_cache

    configure_compile_cache()
    path = tmp_path / "caller.py"
    text = _lowered_from(path, _CALLER, chip)
    assert "tpu_custom_call" in text
    assert _lowered_from(path, _CALLER_MOVED, chip) == text
    jax.config.update("jax_traceback_in_locations_limit", 10)  # the default
    try:
        assert _lowered_from(path, _CALLER, chip) \
            != _lowered_from(path, _CALLER_MOVED, chip)
    finally:
        configure_compile_cache()


def test_delta_rule_layer_is_two_kernels_and_no_loop_on_one_v5e(chip, on_v5e):
    """``models/qwen3_next.py``'s delta-rule mixer at the published widths
    (hidden 2,048, 16 key and 32 value heads of 128, chunks of 64) over
    qwen3-next 8k's 8,192 tokens, its gradient compiled for one v5e with the
    choosers answered for it: the rule is ONE ``ds_gdn_rule_fwd`` and ONE
    ``ds_gdn_rule_bwd``, and neither the lowered nor the compiled program
    holds a loop -- no scan over the 128 chunk boundaries is left; the
    chunks' tables and their inverse stay XLA's. Around the rule (PR 55) the
    mixer is the four kernels of ``ops/pallas/gdn_mix.py`` -- six kernels
    in all -- which read ``in_proj_qkvz``'s columns in place: under
    ``ds.gdn_mix`` the compiled program copies no ``[8192, 8192]`` and no
    ``[8192, 12288]`` bf16 array (the regrouping of the published layout,
    and the concatenate that rebuilt its gradient, are gone)."""
    from benchmark import common
    from deepspeed_tpu.models import qwen3_next as qn

    config = common.load_json("configs", "qwen3-next-80b-a3b.json")
    cfg, _ = common.build_model(config, common.sizes_of(config, "train"))
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.gdn_chunk) == (16, 32, 128, 128, 64)
    mixer = qn.GatedDeltaNet(cfg)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), BF16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, BF16, sharding=chip),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 64, cfg.hidden_size),
                                             BF16)))["params"])
    loss = lambda p, x: jnp.sum(
        mixer.apply({"params": p}, x)[0].astype(jnp.float32))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
    text = lowered.as_text()
    six = ["ds_gdn_gate_bwd", "ds_gdn_gate_fwd", "ds_gdn_premix_bwd",
           "ds_gdn_premix_fwd", "ds_gdn_rule_bwd", "ds_gdn_rule_fwd"]
    assert sorted(re.findall(r'kernel_name = "(ds_\w+)"', text)) == six
    assert "stablehlo.while" not in text
    hlo = lowered.compile().as_text()
    assert sorted(re.findall(r"%(ds_gdn_rule_\w+?)[.\d]* = ", hlo)) == [
        "ds_gdn_rule_bwd", "ds_gdn_rule_fwd"]
    assert sorted(re.findall(r"%(ds_gdn_\w+?)[.\d]* = ", hlo)) == six
    assert not re.search(r" while\(", hlo)
    under_mix = [line for line in hlo.splitlines() if "ds.gdn_mix" in line]
    assert under_mix
    # whatever under the scope results in an array of that size is a kernel
    whole = re.compile(r"= \(?bf16\[(?:1,)?8192,(?:8192|12288)\]")
    assert {re.search(r"%(\w+?)[.\d]* = ", line).group(1)
            for line in under_mix if whole.search(line)
            and " get-tuple-element(" not in line} == {"ds_gdn_premix_bwd"}


# -- the expert layer on one chip, at OLMoE's shapes -------------------------

_GMM = re.compile(r"%(ds_moe_gmm(?:_t)?)[.\d]* = (\w+)\[([\d,]*)\]")


def _grouped_products(hlo):
    """``[(kernel, dtype, dims)]`` of the compiled module's grouped products
    in the small-group kernels; no product may be left in ``ragged-dot`` or
    in a masked dense fallback (``convolution``)."""
    assert "ragged-dot" not in hlo
    assert "convolution" not in "".join(
        line for line in hlo.splitlines() if "moe_gmm" in line)
    found = _GMM.findall(hlo)
    assert all("tpu_custom_call" in line for line in hlo.splitlines()
               if _GMM.search(line))
    return found


def test_olmoe_expert_layer_reaches_the_grouped_kernel_on_one_v5e(
        chip, on_v5e):
    """``models/mixtral.py``'s layer with NO expert axis at OLMoE-1B-7B's
    widths (2 x 4096 tokens, 64 experts of 1024, top-8: a 65,536-row buffer
    in 64 groups of about 1,024 rows), forward and backward, compiles for
    one v5e, and all nine products (three forward, three dx, three dw) are
    the small-group kernels (``ops/pallas/grouped_matmul.py``): six
    ``ds_moe_gmm``, three ``ds_moe_gmm_t``, none ``ragged-dot-*`` (XLA:TPU's
    kernel, 36% of the matmul peak at this shape where these read 60%:
    PERF.md section 6, PR 50) and none a masked dense fallback. What
    ``kernel.ds_moe_gmm.roofline_share`` charges a call is these shapes."""
    import deepspeed_tpu.models.mixtral as mx
    from deepspeed_tpu.models import MixtralConfig

    B, T, HID, INTER, E, K = 2, 4096, 2048, 1024, 64, 8
    cfg = MixtralConfig.olmoe_1b_7b(num_hidden_layers=1)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_local_experts,
            cfg.num_experts_per_tok) == (HID, INTER, E, K)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    args = (struct((B, T, HID), BF16),
            struct((E, HID, INTER), jnp.float32),
            struct((E, INTER, HID), jnp.float32),
            struct((E, HID, INTER), jnp.float32),
            struct((B, T, K), jnp.float32), struct((B, T, K), jnp.int32))

    def loss(x, w1, w2, w3, topk_w, topk_idx):
        out, rows = mx._expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx)
        return jnp.sum(out.astype(jnp.float32) ** 2), rows

    hlo = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
            *args).compile().as_text()
    products = _grouped_products(hlo)
    assert len(products) == 9, products
    # what the roofline's byte count takes each result for
    assert {dtype for _, dtype, _ in products} == {"bf16"}
    shapes = sorted((kernel, dims) for kernel, _, dims in products)
    rows = B * T * K
    assert shapes.count(("ds_moe_gmm", f"{rows},{INTER}")) == 3  # gate, up,
    assert shapes.count(("ds_moe_gmm", f"{rows},{HID}")) == 3    # g @ w2^T;
    assert shapes.count(("ds_moe_gmm_t", f"{E},{HID},{INTER}")) == 2  # down,
    assert shapes.count(("ds_moe_gmm_t", f"{E},{INTER},{HID}")) == 1  # 2 dx


#: a held share's layer at its cell's shapes: ``(tokens, top-k, hidden,
#: intermediate, experts held, experts the router scores, compact rows, the
#: experts' activation)``
HELD_SHARES = {
    "zaya_8k": (8192, 1, 2048, 2048, 8, 17, None, "swiglu"),   # no compact
    "mellum2_8k": (8192, 8, 2304, 896, 8, 64, 16384, "swiglu"),
    "keye_16k": (16384, 8, 2048, 768, 16, 128, 32768, "swiglu"),
    # 1856 columns, 14.5 lanes: one block, the whole dimension (PR 67)
    "nemotron_8k": (8192, 6, 2688, 1856, 8, 128, 6144, "relu2"),
}


@pytest.mark.parametrize("cell", [
    # keye 16k: 35 s cold beside five busy workers (PR 69), the longest of
    # the four; mellum2 8k asks the same of a compact buffer and a SwiGLU
    pytest.param(cell, marks=pytest.mark.slow) if cell == "keye_16k" else cell
    for cell in sorted(HELD_SHARES)])
def test_held_share_reaches_the_small_group_kernels_on_one_v5e(
        chip, on_v5e, cell):
    """``_routed_experts`` at ZAYA1-8B's, Mellum2's, Keye-VL2's and
    Nemotron-3's one-chip shares, forward and backward, compiled for one
    v5e: every grouped product is ``ds_moe_gmm`` / ``ds_moe_gmm_t`` -- nine
    over the one buffer (ZAYA), or nine over the compact buffer and eleven
    over the fallback's rows (an overflowing step's backward computes ``h1``
    and ``h3`` again); an ungated expert (Nemotron-3: no ``w3``) has six and
    seven -- each pair behind its ``conditional``, and each call under the
    layer's ``ds.moe_experts`` scope."""
    import deepspeed_tpu.models.mixtral as mx

    N, K, HID, INTER, G, experts, C, act = HELD_SHARES[cell]
    act = mx._ACTIVATIONS[act]
    assert mx._compact_rows(N * K, G, experts) == C

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    w3 = struct((G, HID, INTER), BF16) if act.gated else None
    args = (struct((N, HID), BF16), struct((G, HID, INTER), BF16),
            struct((G, INTER, HID), BF16), w3,
            struct((N, K), jnp.float32), struct((N, K), jnp.int32))

    def loss(x, w1, w2, w3, topk_w, topk_idx):
        with jax.named_scope("ds.moe_experts"):
            out, rows = mx._routed_experts(x, w1, w2, w3, topk_w, topk_idx,
                                           0, experts, act)
        return jnp.sum(out.astype(jnp.float32) ** 2), rows

    hlo = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4) if act.gated else (0, 1, 2, 4),
        has_aux=True)).lower(*args).compile().as_text()
    products = _grouped_products(hlo)
    rows = sorted(int(dims.split(",")[0]) for kernel, _, dims in products
                  if kernel == "ds_moe_gmm")
    outer = [dims for kernel, _, dims in products if kernel == "ds_moe_gmm_t"]
    assert set(outer) == {f"{G},{HID},{INTER}", f"{G},{INTER},{HID}"}
    firsts = 2 if act.gated else 1            # first products: w1, and w3
    site = 2 * firsts + 2                     # forward, their dx, down, g w2^T
    if C is None:
        assert rows == site * [N * K] and len(outer) == firsts + 1
    else:
        assert rows == site * [C] + (site + firsts) * [N * K]
        assert len(outer) == 2 * (firsts + 1)
        comps = _computations(hlo)
        branches = {name for pair in re.findall(
            r"branch_computations=\{([^}]*)\}", hlo)
            for name in re.findall(r"%([\w.\-]+)", pair)}
        assert all(_GMM.search(comps[name]) for name in branches)
        assert not _GMM.search(next(
            text for text in comps.values() if text.startswith("ENTRY")))
    calls = [line for line in hlo.splitlines() if _GMM.search(line)]
    assert calls and all("ds.moe_experts" in re.search(
        r'op_name="([^"]*)"', line).group(1) for line in calls)


def test_a_step_lowers_each_distinct_grouped_kernel_once(on_v5e):
    """Two expert layers in one step, lowered for the TPU: 22 call sites
    (three forward, two replayed, three dx and three dw products a layer),
    and the module holds SEVEN lowerings of a kernel -- ``ds_moe_gmm`` at
    ``[M, H] x [G, H, I]`` (once more for ``jax.checkpoint``'s replay, whose
    jaxpr is its own), ``[M, I] x [G, I, H]`` and the two transposed
    weights of the backward, ``ds_moe_gmm_t`` at the two gradients' shapes
    -- because the kernels and their visit table are jitted entries: every
    further site is a call of the function the first one lowered, whatever
    the number of layers. A ``pallas_call``
    lowered site by site costs its Mosaic module once a site in every
    cell's set-up (PR 25 read +16.7% ``setup_s`` that way)."""
    import deepspeed_tpu.models.mixtral as mx

    N, K, HID, INTER, G = 4096, 2, 256, 512, 8
    struct = jax.ShapeDtypeStruct
    args = (struct((N, HID), BF16), struct((2, G, HID, INTER), BF16),
            struct((2, G, INTER, HID), BF16), struct((2, G, HID, INTER), BF16),
            struct((N, K), jnp.float32), struct((N, K), jnp.int32))

    @jax.checkpoint
    def layer(x, w1, w2, w3, topk_w, topk_idx):
        return x + mx._routed_experts(x, w1, w2, w3, topk_w, topk_idx, 0)[0]

    def loss(x, w1, w2, w3, topk_w, topk_idx):
        for i in range(2):
            x = layer(x, w1[i], w2[i], w3[i], topk_w, topk_idx)
        return jnp.sum(x.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    text = grad.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    kernels = re.findall(r'kernel_name = "(ds_moe_gmm\w*)"', text)
    assert sorted(kernels) == 5 * ["ds_moe_gmm"] + 2 * ["ds_moe_gmm_t"]
    sites = re.findall(r"call @_(gmm|tgmm|visits)\w*\(", text)
    assert sorted(sites) == 16 * ["gmm"] + 6 * ["tgmm"] + 22 * ["visits"]
    # and the visit tables: one for each kernel at the one row count, and
    # as many again in the replay's jaxpr and the backward's
    assert len(re.findall(r"func.func private @_visits", text)) == 4


# -- Mixtral's expert layer across the four chips ---------------------------

_COLLECTIVE = re.compile(
    r"= (\w+)\[([\d,]*)\]\S* (all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\(")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}


@pytest.mark.parametrize("preset,layout,B", [
    ("mixtral_8x7b", "columns", 4), ("olmoe_1b_7b", "experts", 8)])
def test_mixtral_expert_layer_moves_tokens_not_weights_on_v5e_2x2(
        topo, preset, layout, B):
    """The MoE layer at Mixtral-8x7B widths (4 x 4096 tokens, 8 experts of
    14336, top-2: ``expert_layout`` shards every expert's columns, 3584 a
    chip) and at OLMoE-1B-7B's (8 x 4096 tokens, 64 experts of 1024, top-8:
    16 whole experts a chip), forward and backward, compiles for ``v5e:2x2``
    under ``expert=4`` with the weights sharded as the model's own
    ``partition_rules`` say. In the optimized HLO the products are XLA's own
    grouped matmuls (no ``pallas_call`` of ours in the lowering) over 8 and
    16 groups, and every collective is at most token-sized (B*T*H*4 bytes)
    and never shaped like an expert weight or a chip's share of one — what
    ledger PR 25's breakdown is pinned against, and what a disagreement
    between the rules and the layer's ``in_specs`` would break."""
    import deepspeed_tpu.models.mixtral as mx
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.parallel.topology import set_mesh
    from deepspeed_tpu.runtime.zero.partition import state_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = getattr(MixtralConfig, preset)(num_hidden_layers=1,
                                         scan_layers=False)
    T, HID, INTER, E, K = (4096, cfg.hidden_size, cfg.intermediate_size,
                           cfg.num_local_experts, cfg.num_experts_per_tok)
    assert mx.expert_layout(E, INTER, 4) == layout
    mesh = build_mesh(expert=4, devices=topo.devices)
    set_mesh(mesh)  # the conftest fixture clears it
    tokens = NamedSharding(mesh, P(("data", "expert")))
    shapes = {"w1": (E, HID, INTER), "w2": (E, INTER, HID),
              "w3": (E, HID, INTER)}
    placed, _ = state_shardings(
        {"block_sparse_moe": {n: jax.ShapeDtypeStruct(s, jnp.float32)
                              for n, s in shapes.items()}},
        mesh, None, MixtralForCausalLM.partition_rules(cfg))
    w1, w2, w3 = (jax.ShapeDtypeStruct(
        shapes[n], jnp.float32, sharding=placed["block_sparse_moe"][n])
        for n in ("w1", "w2", "w3"))
    assert "expert" in w1.sharding.spec

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=tokens)

    args = (struct((B, T, HID), BF16), w1, w2, w3,
            struct((B, T, K), jnp.float32), struct((B, T, K), jnp.int32))

    def loss(x, w1, w2, w3, topk_w, topk_idx):
        out, rows = mx._expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx)
        return jnp.sum(out.astype(jnp.float32) ** 2), rows

    lowered = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(*args)
    assert "pallas" not in lowered.as_text().lower()
    hlo = lowered.compile().as_text()
    # three products forward, three dx, three dw: a chip's share of each
    groups, cols = (E, INTER // 4) if layout == "columns" else (E // 4, INTER)
    shapes = sorted(dims for _, dims in re.findall(
        r"%(ragged-dot-none\S*) = \w+\[([\d,]*)\]", hlo))
    rows = B * T * K
    assert shapes.count(f"{rows},{cols}") >= 3       # gate, up, g @ w2^T
    assert shapes.count(f"{rows},{HID}") >= 3        # down, two dx
    assert shapes.count(f"{groups},{HID},{cols}") + \
        shapes.count(f"{groups},{cols},{HID}") >= 3  # dw1, dw3, dw2
    found = [m.groups() for m in map(_COLLECTIVE.search, hlo.splitlines())
             if m]
    assert {kind for _, _, kind in found} >= {"all-gather"}
    token_bytes = B * T * HID * 4
    for dtype, dims, kind in found:
        dims = [int(d) for d in dims.split(",") if d]
        assert math.prod(dims) * _BYTES.get(dtype, 8) <= 1.01 * token_bytes, \
            (kind, dtype, dims)
        assert not (HID in dims and {INTER, INTER // 4} & set(dims)
                    and dims[0] in (E // 4, E)), (kind, dtype, dims)


# -- what the remat'd train step keeps of the flash kernel -------------------

def _llama_two_layers():
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(
        hidden_size=256, intermediate_size=512, num_attention_heads=2,
        num_key_value_heads=1, vocab_size=512, max_position_embeddings=1024,
        sliding_window=512, attention_impl="flash")
    assert cfg.remat and cfg.scan_layers and cfg.remat_policy == "nothing"
    return LlamaForCausalLM(cfg)


def _deepseek_dense_and_two_expert_layers():
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  DeepseekV3ForCausalLM)

    cfg = DeepseekV3Config.tiny(
        hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=512,
        max_position_embeddings=1024, kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, attention_impl="flash",
        remat=True)
    assert (cfg.first_k_dense_replace, cfg.num_hidden_layers) == (1, 3)
    assert cfg.scan_layers and cfg.remat_policy == "nothing"
    return DeepseekV3ForCausalLM(cfg)


def _zaya_two_layers():
    from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM

    cfg = ZayaConfig.tiny(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        head_dim_override=128, router_hidden_size=64,
        moe_intermediate_size=128, n_routed_experts=4, router_experts=8,
        vocab_size=512, max_position_embeddings=1024, num_hidden_layers=2,
        attention_impl="flash", remat=True)
    assert cfg.scan_layers and cfg.remat_policy == "nothing"
    return ZayaForCausalLM(cfg)


@pytest.mark.parametrize("build,sites", [
    (_llama_two_layers, 1),                       # the forward scan's body
    (_deepseek_dense_and_two_expert_layers, 2),   # + the unrolled dense layer
    (_zaya_two_layers, 1),       # unit-length operands, 128 of 256 columns
], ids=["llama_scan2", "deepseek_v3_dense1_scan2", "zaya_scan2"])
def test_remat_train_step_runs_the_flash_forward_once_a_layer(
        chip, on_v5e, monkeypatch, build, sites):
    """The gradient of a scanned, remat'd model under the DEFAULT policy,
    compiled for one v5e: ``ds_flash_fwd`` stands once for each place the
    forward pass calls it and NOT in the backward scan's replay, because
    every policy keeps the kernel's named output and log-sum-exp
    (``layers.resolve_remat_policy``). Before PR 34 the replay held a second
    instance, a third of the attention time of the 8k cells. The backward of
    each call is ONE kernel, ``ds_flash_bwd``, on this chip."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    # the models ask jax.default_backend(), which is the CPU here
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    model = build()
    T = 1024
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=chip),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)))["params"])

    loss = lambda params, ids: model.apply({"params": params}, ids, labels=ids)
    hlo = jax.jit(jax.grad(loss)).lower(params, ids).compile().as_text()
    count = lambda kernel: len(re.findall(
        rf"^\s*%?{kernel}[.\d]* = .*custom-call", hlo, re.M))
    assert count("ds_flash_fwd") == sites
    assert count("ds_flash_bwd") == sites
    assert count("ds_flash_bwd_dq") == count("ds_flash_bwd_dkv") == 0


# -- a held share's compact row buffer (PR 36) -------------------------------

def _computations(hlo):
    """``{computation name: its text}`` of a compiled module's text."""
    parts = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", hlo)
    return {re.match(r"(?:ENTRY )?%([\w.\-]+)", p).group(1): p
            for p in parts if p.startswith(("%", "ENTRY"))}


def test_kimi_expert_layers_run_on_the_compact_buffer_on_one_v5e(
        chip, on_v5e):
    """Two scanned, remat'd ``DeepseekV3MoE`` layers at kimi 8k's widths
    (top-6 of 64 experts, 8 of 1408 held) over a quarter of its 8,192
    tokens -- the kernels' blocks, the branches and the counts follow the
    widths, and XLA:TPU compiles the quarter in half the time (PR 69: 27 s
    and 13 s alone, 55 s beside five busy workers) -- forward and
    backward, compiled for one v5e: every grouped product over the 12,288
    worst-case rows (the cell's 49,152) stands in a ``conditional``'s
    FALLBACK branch, and the branch beside it holds the same layer over
    3,072 rows (the cell's 12,288) — three products
    in the forward scan, two in the replay (the down projection is no
    residual), six in the backward pass. Before PR 36 all eleven ran over
    the worst-case rows, whatever the load. Since PR 50 every one of them is a
    small-group kernel, ``ds_moe_gmm`` / ``ds_moe_gmm_t``, and none is
    XLA:TPU's ``ragged-dot``."""
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  DeepseekV3MoE)
    from deepspeed_tpu.models.layers import resolve_remat_policy
    import deepspeed_tpu.models.mixtral as mx

    T, HID, INTER, G, K = 2048, 2048, 1408, 8, 6
    cfg = DeepseekV3Config.kimi_vl_a3b(n_routed_experts=G, router_experts=64)
    assert (cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok) == (HID, INTER, K)
    full, C = T * K, mx._compact_rows(T * K, G, 64)
    assert (full, C) == (12288, 3072)
    assert mx._compact_rows(8192 * K, G, 64) == 12288   # the cell's own
    layer = DeepseekV3MoE(cfg)
    x = jax.ShapeDtypeStruct((1, T, HID), BF16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((2, *s.shape), BF16, sharding=chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, T, HID), BF16)))["params"])

    def loss(params, x):
        body = lambda x, p: (x + layer.apply({"params": p}, x)[0], None)
        y, _ = jax.lax.scan(jax.checkpoint(
            body, prevent_cse=False, policy=resolve_remat_policy("nothing")),
            x, params)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    comps = _computations(hlo)
    branches = [re.findall(r"%([\w.\-]+)", pair) for pair in re.findall(
        r"branch_computations=\{([^}]*)\}", hlo)]
    assert len(branches) == 3 and all(len(b) == 2 for b in branches)
    fallback = {b[0] for b in branches}     # index 0: the predicate is false
    compact = {b[1] for b in branches}
    assert len(_grouped_products(hlo)) == 11 + 13
    rows_of = lambda text: [int(dims.split(",")[0])
                            for _, _, dims in _GMM.findall(text)]
    counts = {"compact": [], "fallback": []}
    for name, text in comps.items():
        rows = [r for r in rows_of(text) if r != G]     # not the dw products
        if name in fallback:
            assert rows and set(rows) == {full}, (name, rows)
            counts["fallback"].append(len(rows_of(text)))
        elif name in compact:
            assert rows and set(rows) == {C}, (name, rows)
            counts["compact"].append(len(rows_of(text)))
        else:
            assert not rows_of(text), (name, rows_of(text))
    assert sorted(counts["compact"]) == [2, 3, 6]
    # an overflowing step's backward pass computes h1 and h3 again
    assert sorted(counts["fallback"]) == [2, 3, 8]


def _mixtral_two_layers():
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM

    return MixtralForCausalLM(MixtralConfig.tiny(
        hidden_size=256, intermediate_size=128, num_attention_heads=2,
        num_key_value_heads=1, vocab_size=512, max_position_embeddings=1024,
        attention_impl="flash", remat=True))


def _deepseek_eighth_share():
    model = _deepseek_dense_and_two_expert_layers()
    return type(model)(dataclasses.replace(
        model.config, router_experts=64, num_experts_per_tok=6))


@pytest.mark.parametrize("build,engages", [
    (_llama_two_layers, False), (_mixtral_two_layers, False),
    (_zaya_two_layers, False),
    (_deepseek_dense_and_two_expert_layers, False),     # 8 of 8
    (_deepseek_eighth_share, True),                     # 8 of 64
], ids=["llama", "mixtral", "zaya_4_of_9", "deepseek_v3_8_of_8",
        "deepseek_v3_8_of_64"])
def test_compact_buffer_changes_no_other_lowered_gradient(
        chip, monkeypatch, build, engages):
    """The gradient of each model family lowered for one v5e, flash kernels
    in, is the same text with the compact buffer's rule switched off — no
    ``case`` in it — for every family but a DeepSeek-V3 share of at most a
    quarter. (Against the PARENT the texts were compared once, at one path:
    PERF.md section 6, PR 36; a Mosaic payload holds its caller's line
    numbers, so the rule and its functions stand at ``mixtral.py``'s end.)"""
    import deepspeed_tpu.models.mixtral as mx
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    model = build()
    T = 1024
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=chip),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)))["params"])
    loss = lambda params, ids: model.apply({"params": params}, ids, labels=ids)
    texts = []
    for rule in (mx._compact_rows, lambda *a: None):
        monkeypatch.setattr(mx, "_compact_rows", rule)
        # one line lowers both: a Mosaic payload holds this frame's too
        texts.append(jax.jit(jax.grad(loss)).lower(params, ids).as_text())
    with_rule, without = texts
    assert ("stablehlo.case" in with_rule) == engages
    assert "stablehlo.case" not in without
    assert (with_rule == without) == (not engages)


# -- what a remat'ed block keeps where the chip has room (PR 57) -------------

def test_kept_names_cost_train_8ks_step_no_more_than_the_rule_counts(
        chip, on_v5e, monkeypatch):
    """A two-layer Llama at train.8k's widths (hidden 4096, 14336 wide MLP,
    32 / 8 heads of 128, 8,192 tokens, flash kernels in): its gradient
    compiled for one v5e with both offered names kept holds no gate, up, q, k
    or v product in the backward scan's replay, and ``memory_analysis()``'s
    temp stands above the plain step's by no more than ``REMAT_FACTOR`` times
    the bytes the rule counted -- the reading the factor was fixed on (the
    engine's whole step on the chip read 1.41 times: PERF.md section 3)."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.models.layers import REMAT_FACTOR, remat_room

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    T = 8192
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)

    def compiled(budget):
        # a model, and so a function, of its own: jax keeps a trace
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=4096, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=2, num_attention_heads=H,
            num_key_value_heads=HKV, max_position_embeddings=T,
            attention_impl="flash", loss_chunk=1024))
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=chip),
            jax.eval_shape(lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
        loss = lambda params, ids: model.apply({"params": params}, ids,
                                               labels=ids)
        with remat_room(budget) as kept:
            program = jax.jit(jax.grad(loss)).lower(params, ids).compile()
        return dict(kept), program

    kept, named = compiled(4 * 10 ** 9)
    nothing, plain = compiled(0)
    assert nothing == {}
    assert kept == {"ds_mlp_gate_up": 2 * 2 * T * 14336 * 2,
                    "ds_attn_qkv": 2 * T * (H + 2 * HKV) * D * 2}
    wide = lambda hlo: len(re.findall(
        r"= bf16\[8192,14336\]\S* (?:convolution|dot)\(", hlo))
    assert wide(plain.as_text()) - wide(named.as_text()) == 2
    rise = named.memory_analysis().temp_size_in_bytes \
        - plain.memory_analysis().temp_size_in_bytes
    assert 0 < rise <= REMAT_FACTOR * sum(kept.values())


@pytest.mark.slow   # 97 s cold beside five busy workers (PR 69): two whole
# gradient programs at kimi 8k's widths; the same question is asked in
# tier-1 of train 8k's step, above, at 43 s
def test_kept_names_cost_kimi_8ks_step_no_more_than_the_rule_counts(
        chip, on_v5e, monkeypatch):
    """One dense and two scanned expert layers at kimi 8k's widths (hidden
    2048, 16 heads of 192 / 128 over a 512 latent, 8 of 64 experts of 1408
    held on a compact buffer of 12,288 rows, two shared experts, 8,192
    tokens, flash and grouped kernels in) -- the cell nearest the margin:
    its gradient compiled for one v5e with all five offered names kept
    calls ``ds_moe_gmm`` less often (the replay's gate and up products, in
    either branch of its ``cond``) and holds no shared-expert gate or up
    product in the replay, and ``memory_analysis()``'s temp stands above the
    plain step's by no more than ``REMAT_FACTOR`` times the bytes the rule
    counted. The unrolled dense layer offers nothing (XLA merges its replay
    with its forward pass): the offer counts the scanned layers alone."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.models import deepseek_v3 as dsv3
    from deepspeed_tpu.models.layers import REMAT_FACTOR, remat_room

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    T, layers = 8192, 2
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=chip)

    def compiled(budget):
        # a model, and so a function, of its own: jax keeps a trace
        model = dsv3.DeepseekV3ForCausalLM(dsv3.DeepseekV3Config.kimi_vl_a3b(
            vocab_size=4096, num_hidden_layers=1 + layers,
            n_routed_experts=8, router_experts=64, max_position_embeddings=T,
            attention_impl="flash", loss_chunk=1024))
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=chip),
            jax.eval_shape(lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
        loss = lambda params, ids: model.apply({"params": params}, ids,
                                               labels=ids)
        with remat_room(budget) as kept:
            program = jax.jit(jax.grad(loss)).lower(params, ids).compile()
        return dict(kept), program

    kept, named = compiled(4 * 10 ** 9)
    nothing, plain = compiled(0)
    assert nothing == {}
    column = layers * T * 2                     # bf16 bytes a column kept
    assert kept == {
        "ds_attn_o_proj": 2048 * column,
        "ds_mlp_gate_up": 2 * 2 * 1408 * column,
        "ds_attn_qkv": 16 * (192 + 192 + 128) * column,
        "ds_moe_gate_up": layers * 2 * 12288 * 1408 * 2,
        "ds_moe_rows": layers * (12288 * 2048 * 2 + 4 * (3 * T * 6 + 8))}
    plain_hlo, named_hlo = plain.as_text(), named.as_text()
    gate_up = lambda hlo: sum(
        kernel == "ds_moe_gmm" and dims.endswith(",1408")
        for kernel, _, dims in _grouped_products(hlo))
    assert gate_up(named_hlo) > 0
    assert gate_up(plain_hlo) - gate_up(named_hlo) == 4
    shared = lambda hlo: len(re.findall(
        r"= bf16\[8192,2816\]\S* (?:convolution|dot)\(", hlo))
    assert shared(plain_hlo) - shared(named_hlo) == 2
    rise = named.memory_analysis().temp_size_in_bytes \
        - plain.memory_analysis().temp_size_in_bytes
    assert 0 < rise <= REMAT_FACTOR * sum(kept.values())
