"""Operations a configuration's forward and backward passes REQUIRE per
token, from its shapes. Recomputed operations (remat) do not count, and a
sparse-expert layer counts only the experts a token is routed to — what the
program actually executes is its own affair, and shows as a lower MFU."""


def mean_attended_keys(seq_len, window=None):
    """Mean over query positions 0..T-1 of the keys a causal (optionally
    sliding-window) query attends: position i sees min(i + 1, window)."""
    T = int(seq_len)
    if not window or window >= T:
        return (T + 1) / 2.0
    W = int(window)
    return (W * (W + 1) / 2.0 + (T - W) * W) / T


def forward_flops_per_token(cfg, seq_len):
    """Multiply-adds x 2 of one token's forward pass through ``cfg`` (the
    dict of a configs/*.json ``sizes``) in a packed sequence of
    ``seq_len``."""
    H = cfg["hidden_size"]
    Hq = cfg["num_attention_heads"]
    Hkv = cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or H // Hq
    I = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    V = cfg["vocab_size"]
    proj = 2 * H * (Hq * D) * 2 + 2 * H * (Hkv * D) * 2   # q, o; k, v
    attn = 2 * 2 * Hq * D * mean_attended_keys(
        seq_len, cfg.get("sliding_window"))               # scores, values
    mlp = 3 * 2 * H * I
    E = cfg.get("num_local_experts")
    if E:
        mlp = cfg["num_experts_per_tok"] * mlp + 2 * H * E  # routed + router
    return L * (proj + attn + mlp) + 2 * H * V


def train_flops_per_token(cfg, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * forward_flops_per_token(cfg, seq_len)
