"""Operations and bytes of the program's kernels and models, from their
shapes, and the chip's peaks.  No cell reports a kernel's roofline share
yet (neither the fused fit nor the flash kernel has a stable name in
the trace); the kernel counts are kept here so that the PR which names
them only has to divide."""

from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    """The published peaks of a chip; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to peaks.json with its source")
    return table[device_kind]


def dense_fit_ops_bytes(dims, batch: int, steps_per_epoch: int,
                        epochs: int) -> dict:
    """The fused dense-autoencoder fit (`ops/fused_train.py`): `epochs *
    steps_per_epoch` Adam steps over a slice that stays in fast memory.

    Operations: per step and layer (fi -> fo) the forward product, the
    two backward products (weight and input gradient; the first layer
    needs no input gradient) at 2*B*fi*fo each, and ~12 per parameter
    for Adam.  Bytes: what must cross HBM once — the slice and its masks
    in, parameters and both moments in and out, the metrics out."""
    layers = list(zip(dims[:-1], dims[1:]))
    params = sum(fi * fo + fo for fi, fo in layers)
    per_step = sum(2 * batch * fi * fo * (3 if i else 2)
                   for i, (fi, fo) in enumerate(layers)) + 12 * params
    steps = steps_per_epoch * epochs
    data = steps_per_epoch * batch * (dims[0] + 1) * 4
    return {"ops": per_step * steps,
            "bytes": data + 2 * 3 * params * 4 + 2 * epochs * 4,
            "steps": steps, "parameters": params}


def transformer_train_ops(model: dict, window: int, tokens: int) -> float:
    """The operations a pre-norm transformer's forward and backward
    passes require for `tokens` positions in windows of `window`: per
    position and layer 24 d^2 for the four projections and the MLP of
    four times the width (2 per multiply-add) and 2 d T for causal
    attention (a position attends to (T + 1) / 2 keys on average: scores
    and weighted sum at 2 d each); the input and output projections
    once; times 3 for forward and backward.  Recomputation, the
    optimizer, norms and softmax do not count."""
    d, f = model["d_model"], model["features"]
    per_layer = (8 + 4 * model["mlp_ratio"]) * d * d + 2 * d * window
    return 3.0 * tokens * (model["num_layers"] * per_layer + 4 * f * d)
