"""Operations and bytes that a looped stack's training step requires —
`sensorformer-ouro-2.6b`: one set of rotary-attention and gated-MLP
layers run several times a step — from its shapes and from the passes
the program made.

Counted: 2 per multiply-add of every product the algorithm needs, in
the forward pass of ONE pass over the layers, times the passes, and
twice that again for the backward.  Attention is counted by its causal
half (a position meets (T + 1) / 2 keys), at the head's width a score
and a value.  The input Dense runs once a step, the head once a pass.
Not counted: recomputation (every block application is recomputed in
the backward pass), the optimizer, the norms (four a block and one a
pass), rotary turns, softmax, the exit gate, the exit distribution and
its entropy.
"""

from __future__ import annotations


def _layers(cfg: dict) -> int:
    return len(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _attention_parameters(cfg: dict) -> int:
    d, head = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * head \
        + 2 * d * cfg["num_key_value_heads"] * head


def parameters(cfg: dict) -> int:
    """Every parameter held, each counted once however often a step
    applies it: the layers (attention, MLP, four norms), the final norm,
    the two sensor Denses and the exit gate."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    layer = _attention_parameters(cfg) + 3 * d * cfg["intermediate_size"] \
        + 4 * d
    return _layers(cfg) * layer + d + (f * d + d) + (d * f + f) + (d + 1)


def pass_ops_per_token(cfg: dict, window: int) -> dict:
    """Operations one position's forward requires in ONE pass over the
    layers held, by part, in windows of `window` positions."""
    d, f = cfg["hidden_size"], cfg["model"]["features"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    n = _layers(cfg)
    return {
        "attn_proj": n * 2 * _attention_parameters(cfg),
        # scores and values over the heads' features, half the window
        "attn": n * 2 * 2 * width * (window + 1) / 2,
        "mlp": n * 3 * 2 * d * cfg["intermediate_size"],
        "head": 2 * d * f,
    }


def train_ops_bytes(cfg: dict, window: int, tokens: int,
                    passes: float) -> dict:
    """What forward and backward passes over `tokens` positions in
    windows of `window` require where the stack is run `passes` times a
    step: `ops` (three times the forward's), `by_part`, and `bytes` —
    what has to cross HBM at least once a window whatever the schedule:
    every float32 parameter read in each direction of each pass, its
    gradient written once (4 × (2 × passes + 1) bytes), and a block
    application's input written in the forward and read in the
    backward."""
    by_part = {k: 3.0 * tokens * passes * v
               for k, v in pass_ops_per_token(cfg, window).items()}
    by_part["embed"] = 3.0 * tokens * 2 * cfg["model"]["features"] \
        * cfg["hidden_size"]
    blocks = passes * _layers(cfg) * tokens * cfg["hidden_size"] * 4 * 2
    return {"ops": sum(by_part.values()), "by_part": by_part,
            "bytes": tokens / window * 4 * (2 * passes + 1)
            * parameters(cfg) + blocks}
