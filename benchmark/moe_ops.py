"""Operations and bytes that a latent-attention, sparse-expert stack's
training step requires — `sensorformer-kimi-vl-a3b-instruct` — from its
shapes and from the assignments the router made.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass, and twice that again for the backward.  The experts
count by ASSIGNMENT: a token that the router sends to an expert held
here costs that expert's three products once; tokens sent elsewhere
cost this chip nothing, and a tile's padding is not required work.
Attention is counted by its causal half (a position meets (T + 1) / 2
keys), at 192 features a score and 128 a value.  Not counted:
recomputation (every block is recomputed in the backward pass), the
optimizer, norms, rotary turns, softmax, the sort and the gathers.
"""

from __future__ import annotations


def _moe_layers(cfg: dict) -> int:
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i >= cfg["first_k_dense_replace"]
               and i % cfg["moe_layer_freq"] == 0)


def _attention_parameters(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (rank + rope) \
        + rank * h * (nope + dv) + h * dv * d


def parameters(cfg: dict) -> int:
    """Every parameter held here: the file's `n_routed_experts` experts
    a layer, the router over all `published.n_routed_experts`."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    e, routed = cfg["moe_intermediate_size"], \
        cfg["published"]["n_routed_experts"]
    layers, n_moe = cfg["num_hidden_layers"], _moe_layers(cfg)
    moe = (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * 3 * d * e \
        + d * routed + routed
    return layers * (_attention_parameters(cfg) + cfg["kv_lora_rank"]
                     + 2 * d) \
        + n_moe * moe + (layers - n_moe) * 3 * d * cfg["intermediate_size"] \
        + f * d + d + d * f + f + d


def forward_ops_per_token(cfg: dict, window: int) -> dict:
    """Operations one position's forward pass requires, by part, summed
    over the layers held, in windows of `window` positions — without the
    routed experts, which count by assignment (`expert_ops`)."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    layers, n_moe = cfg["num_hidden_layers"], _moe_layers(cfg)
    return {
        "attn_proj": layers * 2 * _attention_parameters(cfg),
        # scores over 192 features and values over 128, half the window
        "attn": layers * 2 * h * (qk + cfg["v_head_dim"]) * (window + 1) / 2,
        "shared": n_moe * 3 * 2 * d * cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        "router": n_moe * 2 * d * cfg["published"]["n_routed_experts"],
        "dense_mlp": (layers - n_moe) * 3 * 2 * d * cfg["intermediate_size"],
        "in_out": 2 * 2 * f * d,
    }


def expert_ops(cfg: dict) -> int:
    """Operations one assignment's forward pass requires: the expert's
    gate, up and down products for one token."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_ops_bytes(cfg: dict, window: int, tokens: int,
                    held_assignments: float) -> dict:
    """What forward and backward passes over `tokens` positions in
    windows of `window` require, `held_assignments` of their
    token-to-expert assignments landing on experts held here (all expert
    layers, every step): `ops` (three times the forward's), `by_part`,
    and `bytes` — what has to cross HBM at least once a window whatever
    the schedule: every float32 parameter read in each pass and its
    gradient written (12 bytes), and a block's input written in the
    forward and read in the backward."""
    by_part = {k: 3.0 * tokens * v
               for k, v in forward_ops_per_token(cfg, window).items()}
    by_part["experts"] = 3.0 * held_assignments * expert_ops(cfg)
    blocks = cfg["num_hidden_layers"] * tokens * cfg["hidden_size"] * 4 * 2
    return {"ops": sum(by_part.values()), "by_part": by_part,
            "bytes": tokens / window * 12 * parameters(cfg) + blocks}


def flash_ops_bytes(kernel: str, B: int, T: int, H: int, D: int, Dv: int,
                    itemsize: int = 4) -> dict:
    """One call of a flash kernel (`fwd`, `bwd_dkv`, `bwd_dq`) on causal
    q, k [B, T, H, D] and v [B, T, H, Dv]: the products its tiles'
    causal half requires, and its operands and results once through HBM
    (the row statistics as the 128-lane rows they are stored as)."""
    half = B * H * T * (T + 1) / 2
    qk, v = B * T * H * D * itemsize, B * T * H * Dv * itemsize
    stat = B * H * T * 128 * 4
    if kernel == "fwd":        # scores; P v
        return {"ops": 2 * half * (D + Dv), "bytes": 2 * qk + 2 * v + stat}
    if kernel == "bwd_dkv":    # scores, dP; dv, dk
        return {"ops": 2 * half * (2 * D + 2 * Dv),
                "bytes": 3 * qk + 3 * v + 2 * stat}
    if kernel == "bwd_dq":     # scores, dP; dq
        return {"ops": 2 * half * (2 * D + Dv),
                "bytes": 3 * qk + 2 * v + 2 * stat}
    raise ValueError(f"no flash kernel {kernel!r}")


def expert_tiles_ops_bytes(cfg: dict, live_rows: int, tiles: int,
                           direction: str = "fwd") -> dict:
    """The experts' tiles of one layer and pass (`ops.moe.experts_apply`):
    the required products by live rows, and what crosses HBM — a tile's
    rows in and out, and its expert's weights once a tile (in the
    backward read twice and their gradients read and written)."""
    d, e = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * d * e * 4
    if direction == "fwd":
        return {"ops": live_rows * expert_ops(cfg),
                "bytes": 2 * live_rows * d * 4 + tiles * weights}
    return {"ops": 3 * live_rows * expert_ops(cfg),
            "bytes": 4 * live_rows * d * 4 + tiles * 4 * weights}
