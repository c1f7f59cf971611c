"""Pallas kernels. Every ``pl.pallas_call`` here passes one of these fixed
names, so a profiler trace names the kernel the same way after any refactor
of the code around it (a trace reader matches the strings; outside this
package only ``models/layers.py resolve_remat_policy`` and the model files
that name a value import any: the checkpoint names)."""

FLASH_FWD = "ds_flash_fwd"
# the flash backward: all three gradients in one walk by kv row where a
# head's dQ fits the chip's VMEM (``flash_attention.fused_backward``), the
# two kernels below where it does not
FLASH_BWD = "ds_flash_bwd"
FLASH_BWD_DQ = "ds_flash_bwd_dq"
FLASH_BWD_DKV = "ds_flash_bwd_dkv"
# ``checkpoint_name``s of the two values the flash backward reads that only
# the forward kernel can produce; ``models/layers.resolve_remat_policy`` keeps
# them under every policy, so a ``jax.checkpoint`` replay holds no forward call
FLASH_OUT = "ds_flash_out"
FLASH_LSE = "ds_flash_lse"
# the indexer's loss of a selection — the head-mean attention probabilities
# from the saved log-sum-exp, reduced against the index scores tile by tile —
# and the scores' gradient (``sa_probs.py``)
SA_PROBS = "ds_sa_probs"
SA_PROBS_BWD = "ds_sa_probs_bwd"
# the indexer's scores of that attention over the causal tiles, and their
# backward's two kernels (``sa_index.py``)
SA_INDEX_FWD = "ds_sa_index_fwd"
SA_INDEX_BWD_DQ = "ds_sa_index_bwd_dq"
SA_INDEX_BWD_DK = "ds_sa_index_bwd_dk"
# ``checkpoint_name`` of a learned selection's bit-packed mask
# (``models/indexed_attention.py``): kept under every remat policy, like the
# two above, so a replay never selects again
SA_MASK = "ds_sa_mask"
# ``checkpoint_name`` of ``ds_sa_probs``' one output, the loss's row
# statistics: kept the same way, so a replay holds no ``ds_sa_probs`` call
SA_KL_ROWS = "ds_sa_kl_rows"
RAGGED_PAGED_ATTENTION = "ds_ragged_paged_attention"
DECODE_ATTENTION = "ds_decode_attention"
PAGED_DECODE_ATTENTION = "ds_paged_decode_attention"
PAGED_PREFILL_ATTENTION = "ds_paged_prefill_attention"
QUANT_MATMUL = "ds_quant_matmul"
INT8_MATMUL = "ds_int8_matmul"
FUSED_ADAM = "ds_fused_adam"
BLOCK_SPARSE_FWD = "ds_block_sparse_fwd"
BLOCK_SPARSE_BWD_DQ = "ds_block_sparse_bwd_dq"
BLOCK_SPARSE_BWD_DKV = "ds_block_sparse_bwd_dkv"
# the selective scan of a state-space layer, a chunk of the sequence at a
# time with the state in VMEM, and its backward (``selective_scan.py``)
SSM_SCAN_FWD = "ds_ssm_scan_fwd"
SSM_SCAN_BWD = "ds_ssm_scan_bwd"
# the expert layer's grouped products on one chip, for small groups
# (``grouped_matmul.py``): rows x a group's weight, and the weights' gradient
MOE_GMM = "ds_moe_gmm"
MOE_GMM_T = "ds_moe_gmm_t"
# the chunked gated delta rule of a linear-attention layer, a chunk's tables
# and a head's matrix state in VMEM, and its backward (``gdn_rule.py``)
GDN_RULE_FWD = "ds_gdn_rule_fwd"
GDN_RULE_BWD = "ds_gdn_rule_bwd"
# what stands around that rule in the layer's mixer (``gdn_mix.py``): the
# convolution, activation and unit length of q, k, v read in place from the
# projection's published columns, and the output's gated norm; a backward
# each (the prefix is not the rule's: a trace reader counts ``ds_gdn_rule_``)
GDN_PREMIX_FWD = "ds_gdn_premix_fwd"
GDN_PREMIX_BWD = "ds_gdn_premix_bwd"
GDN_GATE_FWD = "ds_gdn_gate_fwd"
GDN_GATE_BWD = "ds_gdn_gate_bwd"
# ``checkpoint_name``s a remat'ed block OFFERS, costliest replay a byte
# first: under every policy ``models/layers.keep_for_room`` keeps as many as
# the engine's budget for the trace has room for, and none without one
# (``resolve_remat_policy``); elsewhere they are the identity. The MLP's
# gate and up products, and q, k, v after RoPE (the key/value heads before
# ``repeat_kv``; q and k AHEAD of their norm where they have one, whose
# backward reads its input: the replay then runs the norm and RoPE, not the
# projections) -- ``models/llama.py``; ``models/ouro.py`` offers the same
REMAT_MLP = "ds_mlp_gate_up"
REMAT_QKV = "ds_attn_qkv"
# the attention's output projection, as it joins the residual stream: the
# replay of what follows it in the block (the second norm, the router, an
# expert layer's input) then runs no ``o_proj`` -- ``models/mixtral.py``'s
# and ``models/deepseek_v3.py``'s blocks
REMAT_ATTN_OUT = "ds_attn_o_proj"
# a delta-rule layer's (``models/qwen3_next.py``): the rule's output,
# boundary states and chunk inverse (the replay then runs neither
# ``ds_gdn_rule_fwd`` nor the triangular solve), ``in_proj_qkvz``'s output,
# and what the premix and gate kernels hand on
REMAT_GDN_RULE = "ds_gdn_rule_kept"
REMAT_GDN_QKVZ = "ds_gdn_qkvz"
REMAT_GDN_MIX = "ds_gdn_mix_out"
# an expert layer's (``models/mixtral.py``, named inside the forward rules of
# ``_sorted_experts`` / ``_compact_experts``): the sorted rows' gate and up
# products (the replay then runs no grouped product), and the sorted rows
# with the index vectors the sort made (no ``argsort``, scatter or gather)
REMAT_MOE_UP = "ds_moe_gate_up"
REMAT_MOE_ROWS = "ds_moe_rows"
# a compressed-convolutional-attention layer's (``models/zaya.py``): what
# ``ds.cca_mix`` hands on that its own backward reads -- the first
# convolution's output (the grouped one's weight gradient reads it) and the
# queries and keys ahead of their unit length: the replay then runs neither
# convolution nor the query-key mean
REMAT_CCA_MIX = "ds_cca_mix_out"
# its MLP router's float32 values -- the carried state, its norm, the two
# products ahead of a GELU, the logits, the choice and its weight: the
# replay then runs none of the router's products at the highest precision,
# no GELU's input and no ``top_k``
REMAT_ROUTER = "ds_moe_router_kept"
# and its expert sublayer's output as it joins the scaled residual stream,
# whose output scale's gradient reads it: the replay then runs no forward of
# the expert layer (the down product and the combine) for that sum alone
REMAT_MOE_OUT = "ds_moe_out"
# a scalar-decay state-space layer's (``models/nemotron_h.py``): its input
# projection's output ``[z ; xBC ; dt]``, the widest product of the stack:
# the replay then runs the convolution and the recurrence, not ``in_proj``
REMAT_SSM_IN = "ds_ssm_in_proj"
# a vector-decay delta-rule layer's (``models/kimi_linear.py``): the rule's
# output (each pass of the rule is rematerialised by itself: a replay that
# holds the output runs no rule)
REMAT_KDA_RULE = "ds_kda_rule_out"


# -- the matrix operations a call of a kernel RUNS ---------------------------
# Data for ``profiling/flops_profiler``'s walk of a jaxpr (the train step's
# ``StepCost``), which never enters a kernel's body; it reaches no lowering
# (no ``cost_estimate=`` on a call: that goes into the custom call). An entry
# is ``f(operands, outputs, grid, blocks) -> operations or None``: the shapes
# of the call's operands after its scalar-prefetch arguments and of its
# outputs, the grid, and the block shape of every operand and output in
# order. A kernel without an entry counts 0 and the walk lists it by name
# under ``uncounted``: its products run in VMEM beside element-wise work the
# kernel's own cost file under ``benchmark/`` knows (``ds_ssm_scan_*``,
# ``ds_gdn_*``, ``ds_sa_*``).

def _grouped_product(operands, outputs, grid, blocks):
    # lhs [M, A] x rhs [G, A, B] (or [G, B, A]) -> [M, B], and the weights'
    # gradient lhs [M, A], rhs [M, B] -> [G, A, B]: a row meets one group
    (m, a), out = operands[0], outputs[0]
    return 2 * m * a * out[-1]


def _flash_products(per_tile):
    """``per_tile(D, Dv)``: the contraction widths of the products ONE grid
    step runs on its ``[bq, bk]`` tile. A cut tile is computed whole; a grid
    whose length is data (a mask's tile table) is not counted."""
    def count(operands, outputs, grid, blocks):
        if not all(isinstance(g, int) for g in grid):
            return None
        (_, _, bq, d), (_, _, bk, _), (_, _, _, dv) = blocks[:3]
        steps = 1
        for g in grid:
            steps *= g
        return 2 * steps * bq * bk * per_tile(d, dv)
    return count


MATMUL_FLOPS = {
    MOE_GMM: _grouped_product,
    MOE_GMM_T: _grouped_product,
    # s = q k^T (D) and p v (Dv)
    FLASH_FWD: _flash_products(lambda d, dv: d + dv),
    # s, dk = ds^T q and dq = ds k (D); dv = p^T do and dp = do v^T (Dv)
    FLASH_BWD: _flash_products(lambda d, dv: 3 * d + 2 * dv),
    FLASH_BWD_DQ: _flash_products(lambda d, dv: 2 * d + dv),
    FLASH_BWD_DKV: _flash_products(lambda d, dv: 2 * d + 2 * dv),
}
