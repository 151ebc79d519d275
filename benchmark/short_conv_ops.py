"""Operations and bytes that a gated-short-convolution, rotary
grouped-attention, sparse-expert stack's training step requires —
`sensorformer-lfm2-24b-a2b` — from its shapes and from the assignments
the router made.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass, and twice that again for the backward; the short
convolution by its taps and its two gates (2 · 3 + 2 operations an
element: it has no product for the MXU, and is listed for its bytes).
The experts count by ASSIGNMENT: a token that the router sends to an
expert held here costs that expert's three products once; tokens sent
elsewhere cost this chip nothing, and a tile's padding is not required
work; the layer has no shared expert.  Attention is counted by its
causal half (a position meets (T + 1) / 2 keys), at 64 features a score
and 64 a value.  Not counted: recomputation (every block is recomputed
in the backward pass), the optimizer, norms (the heads' too), rotary
turns, softmax, top-k, the sorts and the gathers.
"""

from __future__ import annotations


def _layers(cfg: dict) -> tuple:
    """(short-convolution, attention, dense, expert) layers held."""
    mixers = cfg["layer_types"][:cfg["num_hidden_layers"]]
    dense = min(cfg["num_dense_layers"], len(mixers))
    return (mixers.count("conv"), mixers.count("full_attention"), dense,
            len(mixers) - dense)


def _attention_parameters(cfg: dict) -> int:
    d = cfg["hidden_size"]
    head = d // cfg["num_attention_heads"]
    return 2 * d * d + 2 * d * cfg["num_key_value_heads"] * head


def _short_conv_parameters(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def parameters(cfg: dict) -> int:
    """Every parameter held here: the file's `num_experts` experts a
    layer, the router over all `published.num_experts`."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    n_conv, n_attn, n_dense, n_moe = _layers(cfg)
    routed = cfg["published"]["num_experts"]
    head = d // cfg["num_attention_heads"]
    moe = cfg["num_experts"] * 3 * d * cfg["moe_intermediate_size"] \
        + d * routed + routed
    return n_conv * (_short_conv_parameters(cfg) + cfg["conv_L_cache"] * d) \
        + n_attn * (_attention_parameters(cfg) + 2 * head) \
        + n_dense * 3 * d * cfg["intermediate_size"] + n_moe * moe \
        + (n_conv + n_attn) * 2 * d + f * d + d + d * f + f + d


def forward_ops_per_token(cfg: dict, window: int) -> dict:
    """Operations one position's forward pass requires, by part, summed
    over the layers held, in windows of `window` positions — without the
    routed experts, which count by assignment (`expert_ops`)."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    n_conv, n_attn, n_dense, n_moe = _layers(cfg)
    return {
        "conv_proj": n_conv * 2 * _short_conv_parameters(cfg),
        # the taps and the two gates, an element
        "short_conv": n_conv * (2 * cfg["conv_L_cache"] + 2) * d,
        "attn_proj": n_attn * 2 * _attention_parameters(cfg),
        # scores and values over a head's features, half the window
        "attn": n_attn * 2 * 2 * d * (window + 1) / 2,
        "dense_mlp": n_dense * 3 * 2 * d * cfg["intermediate_size"],
        "router": n_moe * 2 * d * cfg["published"]["num_experts"],
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


def short_conv_bytes(cfg: dict, tokens: int, itemsize: int = 4) -> dict:
    """Bytes one gated short convolution moves through HBM over `tokens`
    positions, by pass and by form, in streams of tokens x hidden_size
    elements.  `built`: the gates as XLA's multiplies around the
    kernels' calls — forward b, x in and z out; z in and y out; c, y in
    and the gated y out (8 streams); backward (its cotangent d) d, c, y
    in and dc, dy out; z, dy in and dz out; dz, b, x in and db, dx out
    (13).  `fused`: the gates inside the kernels — forward b, x, c in
    and the gated y out (4); backward b, x, c, d in and db, dx, dc out
    (7).  The recomputed forward ahead of a backward is the forward's
    again and is not in the backward's count."""
    stream = tokens * cfg["hidden_size"] * itemsize
    return {"stream": stream,
            "built": {"fwd": 8 * stream, "bwd": 13 * stream},
            "fused": {"fwd": 4 * stream, "bwd": 7 * stream}}


def conv_ops_bytes(kernel: str, B: int, T: int, C: int, K: int,
                   itemsize: int = 4) -> dict:
    """One call of a convolution kernel (`fwd`, `bwd`) without an
    activation on x [B, C, T]: the taps' multiply-adds (again for dx and
    for the tap gradients in the backward), and its operands and results
    once through HBM (x, y; x, dy, dx)."""
    elements = B * T * C
    if kernel == "fwd":
        return {"ops": 2 * K * elements, "bytes": 2 * elements * itemsize}
    if kernel == "bwd":
        return {"ops": 4 * K * elements, "bytes": 3 * elements * itemsize}
    raise ValueError(f"no convolution kernel {kernel!r}")
