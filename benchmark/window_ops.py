"""Operations and bytes that a stack of global and sliding-window
grouped attention with sparse ReLU-gated experts requires in a training
step — `sensorformer-smallthinker-21b-a3b` — from its shapes, its masks
and the assignments the router made; and the three flash kernels'
operations and bytes a call, under the causal mask and under a band.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass, and twice that again for the backward.  Attention is
counted by the keys INSIDE A LAYER'S MASK — a global layer's query
meets (T + 1) / 2 keys on average, a window layer's `min(t + 1, W)`,
at the heads' width a score and a value — not by the tiles a grid
walks: a tile's masked part is not required work.  The experts count by
ASSIGNMENT: a token that the router sends to an expert held here costs
that expert's three products once; tokens sent elsewhere cost this chip
nothing, and a tile's padding is not required work; the layer has no
shared expert.  Not counted: recomputation (every block is recomputed
in the backward pass), the optimizer, norms, rotary turns, softmax,
top-k, the sorts and the gathers, the repeat of k and v over their
query groups.
"""

from __future__ import annotations


def _layouts(cfg: dict) -> tuple:
    """(global layers, window layers) held."""
    slides = cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]
    return len(slides) - sum(slides), sum(slides)


def _attention_parameters(cfg: dict) -> int:
    d, head = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * head \
        + 2 * d * cfg["num_key_value_heads"] * head


def parameters(cfg: dict) -> int:
    """Every parameter held here: the file's `moe_num_primary_experts`
    experts a layer, the router over all the published ones (no bias),
    two norms a layer; the final norm and the two sensor Denses."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    routed = cfg["published"]["moe_num_primary_experts"]
    layer = _attention_parameters(cfg) + d * routed \
        + cfg["moe_num_primary_experts"] * 3 * d * cfg["moe_ffn_hidden_size"] \
        + 2 * d
    return cfg["num_hidden_layers"] * layer + f * d + d + d * f + f + d


def mask_area(T: int, window=None) -> int:
    """The scores of one head the causal mask lets through at length T:
    the triangle's T (T + 1) / 2, or under `window` the band's — query t
    meets `min(t + 1, window)` keys."""
    w = T if window is None else min(window, T)
    return w * (w + 1) // 2 + (T - w) * w


def forward_ops_per_token(cfg: dict, window: int) -> dict:
    """Operations one position's forward pass requires, by part, summed
    over the layers held, in windows of `window` positions — without the
    routed experts, which count by assignment (`expert_ops`)."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    n_global, n_window = _layouts(cfg)
    # scores and values over the heads' features, the keys a query meets
    met = lambda w: 2 * 2 * width * mask_area(window, w) / window  # noqa: E731
    return {
        "attn_proj": (n_global + n_window) * 2 * _attention_parameters(cfg),
        "attn_global": n_global * met(None),
        "attn_window": n_window * met(cfg["sliding_window_size"]),
        "router": (n_global + n_window) * 2 * d
        * cfg["published"]["moe_num_primary_experts"],
        "in_out": 2 * 2 * f * d,
    }


def expert_ops(cfg: dict) -> int:
    """Operations one assignment's forward pass requires: the expert's
    gate, up and down products for one token."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def train_ops_bytes(cfg: dict, window: int, tokens: int,
                    held_assignments: float) -> dict:
    """What forward and backward passes over `tokens` positions in
    windows of `window` require, `held_assignments` of their
    token-to-expert assignments landing on experts held here (all
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


#: the products a tile of each flash kernel makes, each 2 · D operations
#: a score: the forward's scores and P·V; dK/dV's scores, dP, dV and dK;
#: dQ's scores, dP and dQ
_KERNEL_PRODUCTS = {"fwd": 2, "bwd_dkv": 4, "bwd_dq": 3}
#: the [B, T, H·D] arrays a call of each moves through HBM once: q, k,
#: v, out; q, dO, k, v, dk, dv; q, dO, k, v, dq — and its float32 row
#: statistics [B, H, T]: lse; lse and delta
_KERNEL_STREAMS = {"fwd": (4, 1), "bwd_dkv": (6, 2), "bwd_dq": (5, 2)}


def flash_ops_bytes(kernel: str, B: int, T: int, H: int, D: int,
                    window=None, itemsize: int = 4) -> dict:
    """One call of a flash kernel (`fwd`, `bwd_dkv`, `bwd_dq`) on H
    equal heads of D (k and v already repeated over their groups, as the
    kernels see them) under the causal mask, or the band of `window`:
    the operations of the scores INSIDE the mask (recomputed scores in
    the backward kernels are those kernels' own required work), and its
    operands and results once through HBM — a band's call reads every
    key and value once, as the triangle's does."""
    streams, stats = _KERNEL_STREAMS[kernel]
    return {"ops": _KERNEL_PRODUCTS[kernel] * 2 * D * B * H
            * mask_area(T, window),
            "bytes": streams * B * T * H * D * itemsize
            + stats * B * H * T * 4}
