"""Operations and bytes that a stack of Kimi Delta Attention and latent
attention without positions with sparse sigmoid-routed experts requires
in a training step — `sensorformer-kimi-linear-48b-a3b` — from its
shapes, its chunk size and the assignments the router made; and one call
of the chunked gated delta rule's, forward or backward.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass, and twice that again for the backward.  The delta
rule is counted BY THE CHUNKED ALGORITHM at the chunk size the program
ran (`ops/delta.py`'s text has the form): causal halves only — the
strictly lower half of `(K ⊙ e^G)(K ⊙ e^−G)ᵀ`, the lower half with the
diagonal of the queries' — the inverse of `I + A` by its forward
substitution (C³/6 multiply-adds), `W` and `U` by the triangle of `T`,
and the three products with the state a chunk (`W S`, `(Q ⊙ e^G) S`,
`(K ⊙ e^{G_C−G})ᵀ Ṽ`); stepped position by position it would be
4 · K · V a head and position, fewer operations and no product at all.
Latent attention is counted by its causal half (a position meets
(T + 1) / 2 keys), at 192 features a score and 128 a value.  The experts
count by ASSIGNMENT: a token that the router sends to an expert held
here costs that expert's three products once; tokens sent elsewhere cost
this chip nothing, and a tile's padding is not required work.  Not
counted: recomputation (every block is recomputed in the backward pass,
a segment of the scan once more), the optimizer, norms, gates,
exponentials, top-k and the sorts.
"""

from __future__ import annotations


def _layers(cfg: dict) -> tuple:
    """(KDA layers, latent-attention layers, expert layers) held."""
    lin, n = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    routed = sum(1 for i in range(n) if i >= cfg["first_k_dense_replace"]
                 and i % cfg["moe_layer_freq"] == 0)
    return (sum(1 for i in lin["kda_layers"] if i <= n),
            sum(1 for i in lin["full_attn_layers"] if i <= n), routed)


def _kda_products(cfg: dict) -> int:
    """A KDA mixer's kernels that are products: q, k and v's, the two
    gates' low-rank pairs, β's and the output's."""
    d, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    inner, rank = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    return d * 3 * inner + d * (2 * rank + lin["num_heads"]) \
        + 2 * rank * inner + inner * d


def _mla_products(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (rank + rope) \
        + rank * h * (nope + dv) + h * dv * d


def parameters(cfg: dict) -> int:
    """Every parameter held here: a KDA mixer's products, its taps,
    `A_log`, `dt_bias` and the heads' norm; a latent mixer's products
    and its latent's norm; the dense MLP; the file's `num_experts`
    experts a layer, the shared ones, the router over all
    `published.num_experts` and its bias; two norms a layer; the final
    norm and the two sensor Denses."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    lin, e = cfg["linear_attn_config"], cfg["moe_intermediate_size"]
    inner = lin["num_heads"] * lin["head_dim"]
    n_kda, n_mla, n_moe = _layers(cfg)
    kda = _kda_products(cfg) + lin["short_conv_kernel_size"] * 3 * inner \
        + lin["num_heads"] + inner + lin["head_dim"]
    routed = cfg["published"]["num_experts"]
    moe = (cfg["num_experts"] + cfg["num_shared_experts"]) * 3 * d * e \
        + d * routed + routed
    layers = cfg["num_hidden_layers"]
    return n_kda * kda + n_mla * (_mla_products(cfg) + cfg["kv_lora_rank"]) \
        + n_moe * moe + (layers - n_moe) * 3 * d * cfg["intermediate_size"] \
        + layers * 2 * d + f * d + d + d * f + f + d


def kda_chunk_ops(C: int, K: int, V: int) -> float:
    """One head's chunk of C positions, forward: the two score matrices'
    causal halves, the inverse by substitution, W and U by T's triangle,
    the three products with the K × V state, and the scores' product
    with Ṽ."""
    lower, strict = C * (C + 1) / 2, C * (C - 1) / 2
    return 2 * K * (strict + lower) + 2 * C ** 3 / 6 \
        + 2 * lower * (K + V) + 3 * 2 * C * K * V + 2 * lower * V


def kda_ops_bytes(B: int, T: int, H: int, K: int, V: int, chunk: int,
                  direction: str = "fwd", itemsize: int = 4) -> dict:
    """One call of the chunked gated delta rule on q, k, g [B, T, H, K],
    v [B, T, H, V] and β [B, T, H]: the operations of the algorithm
    above at chunks of `chunk` (T in whole chunks), and its operands and
    results once through HBM — forward q, k, g, v, β in and o out;
    backward those and o's cotangent in, five cotangents out."""
    chunks = -(-T // chunk)
    ops = B * H * chunks * kda_chunk_ops(chunk, K, V)
    rows = B * T * H * itemsize
    if direction == "fwd":
        return {"ops": ops, "bytes": rows * (3 * K + V + 1 + V)}
    return {"ops": 2 * ops, "bytes": rows * (2 * (3 * K + V + 1) + V)}


def forward_ops_per_token(cfg: dict, window: int, chunk: int,
                          kda_layers: int = None) -> dict:
    """Operations one position's forward pass requires, by part, summed
    over the layers held, in windows of `window` positions and chunks
    of `chunk` — without the routed experts, which count by assignment
    (`expert_ops`).  `kda_layers`: the KDA layers the program's own
    gauge counted (None: the file's)."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    lin, h = cfg["linear_attn_config"], cfg["num_attention_heads"]
    heads, width = lin["num_heads"], lin["head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    n_kda, n_mla, n_moe = _layers(cfg)
    n_kda = n_kda if kda_layers is None else kda_layers
    return {
        "kda_proj": n_kda * 2 * _kda_products(cfg),
        "kda_conv": n_kda * 2 * lin["short_conv_kernel_size"] * 3
        * heads * width,
        "kda_scan": n_kda * heads * kda_chunk_ops(chunk, width, width)
        / chunk,
        "mla_proj": n_mla * 2 * _mla_products(cfg),
        # scores over 192 features and values over 128, half the window
        "mla_attn": n_mla * 2 * h * (qk + cfg["v_head_dim"])
        * (window + 1) / 2,
        "dense_mlp": (cfg["num_hidden_layers"] - n_moe) * 3 * 2 * d
        * cfg["intermediate_size"],
        "shared": n_moe * 3 * 2 * d * cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        "router": n_moe * 2 * d * cfg["published"]["num_experts"],
        "in_out": 2 * 2 * f * d,
    }


def expert_ops(cfg: dict) -> int:
    """Operations one assignment's forward pass requires: the expert's
    gate, up and down products for one token."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_ops_bytes(cfg: dict, window: int, tokens: int,
                    held_assignments: float, chunk: int = None,
                    kda_layers: int = None) -> dict:
    """What forward and backward passes over `tokens` positions in
    windows of `window` require, the delta rule in chunks of `chunk`
    (None: the file's `kda_chunk_size`), `held_assignments` of the
    token-to-expert assignments landing on experts held here (all expert
    layers, every step): `ops` (three times the forward's), `by_part`,
    and `bytes` — what has to cross HBM at least once a window whatever
    the schedule: every float32 parameter read in each pass and its
    gradient written (12 bytes), and a block's input written in the
    forward and read in the backward."""
    by_part = {k: 3.0 * tokens * v for k, v in forward_ops_per_token(
        cfg, window, chunk or cfg["kda_chunk_size"], kda_layers).items()}
    by_part["experts"] = 3.0 * held_assignments * expert_ops(cfg)
    blocks = cfg["num_hidden_layers"] * tokens * cfg["hidden_size"] * 4 * 2
    return {"ops": sum(by_part.values()), "by_part": by_part,
            "bytes": tokens / window * 12 * parameters(cfg) + blocks}
