"""Operations and bytes that a one-part-a-layer hybrid stack's training
step requires — Mamba-2 mixers, grouped-query attention and LatentMoE
layers (experts in a latent beside a full-width shared expert), as
`sensorformer-nemotron-3-super-120b-a12b` states it — from its shapes
and from the assignments the router made.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass, and twice that again for the backward.  The heads,
groups and experts are those HELD here (the file's counts).  The experts
count by ASSIGNMENT, in the latent: a token that the router sends to an
expert held here costs that expert's two products once; tokens sent
elsewhere cost this chip nothing, and a tile's padding is not required
work.  The scan is counted as the chunked algorithm states it and
attention by its causal half, as `hybrid_ops.py` counts them.  Not
counted: recomputation (every block is recomputed in the backward
pass), the optimizer, norms, gates, softmax, decays, top-k, the sort
and the gathers.
"""

from __future__ import annotations


def _letters(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _mamba(cfg: dict) -> tuple:
    """(inner width, the convolved stream's channels, in_proj's columns)."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, conv, inner + conv + cfg["mamba_num_heads"]


def _shared(cfg: dict) -> int:
    return cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]


def parameters(cfg: dict) -> int:
    """Every parameter held here: the file's `n_routed_experts` experts
    a layer, the router over all `published.n_routed_experts`, and the
    heads and groups the file counts."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    inner, conv, columns = _mamba(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    latent, routed = cfg["moe_latent_size"], \
        cfg["published"]["n_routed_experts"]
    layer = {
        "M": d * columns + (cfg["conv_kernel"] + 1) * conv
        + 3 * cfg["mamba_num_heads"] + inner + inner * d + d,
        "*": 2 * d * q + 2 * d * kv + d,
        "E": d * routed + routed + 2 * d * latent + 2 * d * _shared(cfg)
        + cfg["n_routed_experts"] * 2 * latent
        * cfg["moe_intermediate_size"] + d}
    return sum(layer[c] for c in _letters(cfg)) + f * d + d + d * f + f + d


def forward_ops_per_token(cfg: dict, window: int) -> dict:
    """Operations one position's forward pass requires, by part, summed
    over the layers held, in windows of `window` positions — without the
    routed experts, which count by assignment (`expert_ops`)."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    letters = _letters(cfg)
    n_mamba, n_attn, n_moe = (letters.count(c) for c in "M*E")
    inner, conv, columns = _mamba(cfg)
    state = cfg["n_groups"] * cfg["ssm_state_size"]
    q = min(cfg["chunk_size"], window)
    chunks = -(-window // q)
    scan = (
        2 * state * (q + 1) / 2      # C Bᵀ, a group's heads sharing it
        + 2 * inner * (q + 1) / 2    # the masked, decayed product with Δx
        + 2 * inner * cfg["ssm_state_size"]   # the state a chunk leaves
        + 2 * inner * cfg["ssm_state_size"]   # the entering state through C
        # states carried over the chunks between, a window
        + 2 * inner * cfg["ssm_state_size"] * chunks * (chunks - 1) / 2
        / window)
    heads = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {
        "ssm_proj": n_mamba * 2 * (d * columns + inner * d),
        "conv": n_mamba * 2 * cfg["conv_kernel"] * conv,
        "ssd": n_mamba * scan,
        # q and o, k and v, then scores and weighted sum over half T
        "attn": n_attn * (2 * 2 * d * heads + 2 * 2 * d * kv
                          + 2 * 2 * heads * (window + 1) / 2),
        "router": n_moe * 2 * d * cfg["published"]["n_routed_experts"],
        "latent_proj": n_moe * 2 * 2 * d * cfg["moe_latent_size"],
        "shared": n_moe * 2 * 2 * d * _shared(cfg),
        "in_out": 2 * 2 * f * d,
    }


def expert_ops(cfg: dict) -> int:
    """Operations one assignment's forward pass requires: the expert's
    up and down products for one token's latent."""
    return 2 * 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


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
