"""Operations and bytes that a hybrid stack's training step requires —
Mamba-2 (chunked state-space) mixers beside grouped-query attention, as
`sensorformer-granite-4.0-h-micro` states it — from its shapes.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass, and twice that again for the backward.  Not counted:
recomputation (every block is recomputed in the backward pass: a fifth
pass the algorithm does not require), the optimizer, the norms, gates,
softmax, decays and other elementwise work.  The chunked scan is
counted as the chunked algorithm states it, causal halves only: a
position meets (Q + 1) / 2 positions of its chunk on average, as one of
attention's meets (T + 1) / 2 keys (`kernels.transformer_train_ops`
counts attention the same way).
"""

from __future__ import annotations


def _layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def forward_ops_per_token(cfg: dict, window: int) -> dict:
    """Operations one position's forward pass requires, by part, summed
    over the layers held, in windows of `window` positions."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    heads, state = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = heads * cfg["mamba_d_head"]
    q = min(cfg["mamba_chunk_size"], window)
    chunks = -(-window // q)
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    kinds = _layer_kinds(cfg)
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    scan = (
        2 * state * (q + 1) / 2      # C Bᵀ, shared by the heads
        + 2 * inner * (q + 1) / 2    # the masked, decayed product with Δx
        + 2 * inner * state          # the state a chunk leaves behind
        + 2 * inner * state          # the entering state read through C
        # states carried over the chunks between, a window
        + 2 * inner * state * chunks * (chunks - 1) / 2 / window)
    return {
        "ssm_proj": n_mamba * 2 * d * (2 * inner + 2 * state + heads)
        + n_mamba * 2 * inner * d,
        "conv": n_mamba * 2 * cfg["mamba_d_conv"] * (inner + 2 * state),
        "ssd": n_mamba * scan,
        # q and o, k and v, then scores and weighted sum over half T
        "attn": n_attn * (2 * 2 * d * d + 2 * 2 * d * kv
                          + 2 * 2 * d * (window + 1) / 2),
        "mlp": len(kinds) * 3 * 2 * d * cfg["shared_intermediate_size"],
        "in_out": 2 * 2 * f * d,
    }


def parameters(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    heads, state = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = heads * cfg["mamba_d_head"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    conv = inner + 2 * state
    mixer = {"mamba": d * (2 * inner + 2 * state + heads)
             + (cfg["mamba_d_conv"] + 1) * conv + 3 * heads + inner
             + inner * d,
             "attention": 2 * d * d + 2 * d * kv}
    shared = 3 * d * cfg["shared_intermediate_size"] + 2 * d
    return sum(mixer[k] + shared for k in _layer_kinds(cfg)) \
        + f * d + d + d * f + f + d


def train_ops_bytes(cfg: dict, window: int, tokens: int) -> dict:
    """What forward and backward passes over `tokens` positions in
    windows of `window` require: `ops` (three times the forward's),
    `by_part`, and `bytes` — what has to cross HBM at least once a
    window whatever the schedule: every float32 parameter read in each
    pass and its gradient written (12 bytes), and a block's input
    written in the forward and read in the backward."""
    per_token = forward_ops_per_token(cfg, window)
    by_part = {k: 3.0 * tokens * v for k, v in per_token.items()}
    windows = tokens / window
    blocks = len(_layer_kinds(cfg)) * tokens * cfg["hidden_size"] * 4 * 2
    return {"ops": sum(by_part.values()), "by_part": by_part,
            "bytes": windows * 12 * parameters(cfg) + blocks}
