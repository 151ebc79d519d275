"""sensorformer-lfm2-24b-a2b: the plain reference, and the adapter that
runs the fit leg of `run_streaming_app`'s train mode (cli/_app.py) as
`cli/lstm.py` instantiates it, with the program's `SensorHybrid` at the
source's widths as `make_model`, job after job.

The reference: LFM2's layer equations as the source's `config.json`
states them (the configuration's file has them in words), in
`jax.numpy`.  Every layer is `h + mixer(RMSNorm(h))`,
`h + ffn(RMSNorm(h))`:

- `conv`, the gated short convolution: `[b, c, x] = u W_in`, the
  convolution of `b ⊙ x` AS THREE SHIFTED PRODUCTS SUMMED (tap j meets
  the position 2 − j back; zeros before the window), no bias and no
  activation, `(c ⊙ y) W_out` — no kernel, no transposed layout:
  nothing of the program's convolution;
- `full_attention`: grouped queries, a weight-only RMSNorm over each
  query and key head's 64 features, rotary positions over the whole
  head written out here (neighbouring pairs, the program's pairing: the
  configuration's file says why that is the family's up to one fixed
  permutation), one key/value group and 1,024 queries at a time so that
  T = 8,192 fits;
- the dense gated MLP, or the expert layer: the sparse-expert
  configuration's sigmoid router, EVERY EXPERT HELD APPLIED DENSELY TO
  EVERY TOKEN and weighted by a routing weight that is zero where it
  was not selected — no sort, no tiles — and NO shared expert.

A chip's share is given to the reference as it is to the program: the
file's `num_experts` counts the experts held, and the same functions
compute the uncut layer when handed all 64
(`tests/test_lfm2_stack.py` adds the shares up to it).

What is the same mathematics is imported, not written again: the
router, the masked loss, Adam, the fit and the adapter
(`sensorformer-kimi-vl-a3b-instruct.py`).  That file's fit and adapter
are around ITS block and ITS names for the experts' counts; this file
hands its own instance of that module this block (`_init`, `_forward`,
`hybrid_config`, and `_held`, which reads this source's key) and takes
the rest as it stands.  Imports nothing of the program but in the
adapter.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

# the sparse-expert configuration's reference and adapter — an instance
# of its own, so that the block set on it below is this file's alone
_spec = importlib.util.spec_from_file_location(
    "bench_sensorformer_kimi_for_lfm2", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-kimi-vl-a3b-instruct.py"))
_km = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_km)
CFG = _km.CFG     # this run's configuration file, set by use()
STD, Q_BLOCK = _km.STD, _km.Q_BLOCK   # seeded kernels' deviation; queries
#                                       a block of the plain attention
MIXERS = {"conv": "short_conv", "full_attention": "attention"}

use = _km.use


def _held() -> tuple:
    """(first, count, routed over): the experts held here of all."""
    return (CFG["experts_held"]["first"], CFG["num_experts"],
            CFG["published"]["num_experts"])


def _mixers() -> tuple:
    return tuple(CFG["layer_types"][:CFG["num_hidden_layers"]])


def _ffn_kinds() -> tuple:
    """The leading `num_dense_layers` have the dense MLP, the rest the
    expert layer."""
    return tuple("dense_ffn" if i < CFG["num_dense_layers"] else "moe_ffn"
                 for i in range(CFG["num_hidden_layers"]))


def _head_dim() -> int:
    return CFG["hidden_size"] // CFG["num_attention_heads"]


# ------------------------------------------------------------ reference
def _init(key):
    d, f = CFG["hidden_size"], CFG["model"]["features"]
    qh, kvh, hd = CFG["num_attention_heads"], CFG["num_key_value_heads"], \
        _head_dim()
    e = CFG["moe_intermediate_size"]
    _, held, routed = _held()
    mixers, ffns = _mixers(), _ffn_kinds()
    keys = iter(jax.random.split(key, 12 * len(mixers) + 2))

    def normal(*shape):
        return STD * jax.random.normal(next(keys), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm(width):
        return {"scale": jnp.ones((width,), jnp.float32)}

    def short_conv():
        return {"in_proj": kernel(d, 3 * d),
                "conv_kernel": normal(CFG["conv_L_cache"], d),
                "out_proj": kernel(d, d)}

    def attention():
        return {"q": kernel(d, qh * hd), "k": kernel(d, kvh * hd),
                "v": kernel(d, kvh * hd), "o": kernel(qh * hd, d),
                "q_norm": norm(hd), "k_norm": norm(hd)}

    # the tree the program's flax module builds (models/hybrid.py)
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(d)}
    for i, (mixer, ffn) in enumerate(zip(mixers, ffns)):
        layer = {"norm1": norm(d), "norm2": norm(d),
                 "mixer": short_conv() if mixer == "conv" else attention()}
        if ffn == "moe_ffn":
            layer["moe"] = {
                "router": normal(d, routed), "router_bias": normal(routed),
                "experts_in": normal(held, d, 2 * e),
                "experts_out": normal(held, e, d)}
        else:
            layer["mlp_in"] = kernel(d, 2 * CFG["intermediate_size"])
            layer["mlp_out"] = kernel(CFG["intermediate_size"], d)
        out[f"layer{i}"] = layer
    return out


def _rms_norm(p, x):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + jnp.asarray(CFG["norm_eps"], x.dtype)) \
        * p["scale"]


def _short_conv(p, u):
    """(c ⊙ conv(b ⊙ x)) W_out with [b, c, x] = u W_in: tap j of the K
    meets the position K − 1 − j back, and what lies before the window
    is zero — K shifted products, summed."""
    T = u.shape[1]
    b, c, x = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    z = b * x
    taps = p["conv_kernel"]
    K = taps.shape[0]
    y = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j
        y = y + jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :T] * taps[j]
    return (c * y) @ p["out_proj"]["kernel"]


def _rotary(x, theta):
    """x [B, T, H, R]: features (2i, 2i+1) turned by t · θ^(−2i/R)."""
    T, R = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, R, 2) / R))
    angle = jnp.asarray(np.arange(T)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2].astype(jnp.float32), \
        x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(p, u):
    """Query head i reads key/value head i // (heads / groups); normed
    and turned heads; a group and a block of queries at a time."""
    B, T, _ = u.shape
    qh, g, hd = CFG["num_attention_heads"], CFG["num_key_value_heads"], \
        _head_dim()
    theta = CFG["rope_parameters"]["rope_theta"]
    q = _rms_norm(p["q_norm"], (u @ p["q"]["kernel"]).reshape(B, T, qh, hd))
    k = _rms_norm(p["k_norm"], (u @ p["k"]["kernel"]).reshape(B, T, g, hd))
    q = _rotary(q, theta).reshape(B, T, g, qh // g, hd)
    k = _rotary(k, theta)
    v = (u @ p["v"]["kernel"]).reshape(B, T, g, hd)
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    pos_k = jnp.arange(T)

    @jax.checkpoint
    def block(qb, kg, vg, start):              # [B, blk, R, D], [B, T, D]
        s = jnp.einsum("bqrd,bkd->brqk", qb, kg) \
            * jnp.asarray(1.0 / math.sqrt(hd), qb.dtype)
        causal = (start + jnp.arange(blk))[:, None] >= pos_k[None, :]
        s = jnp.where(causal, s.astype(jnp.float32), -1e30)
        return jnp.einsum("brqk,bkd->bqrd",
                          jax.nn.softmax(s, axis=-1).astype(vg.dtype), vg)

    def group(args):
        qg, kg, vg = args                      # [B, T, R, D], [B, T, D]
        qb = jnp.moveaxis(qg.reshape((B, T // blk, blk) + qg.shape[2:]),
                          1, 0)
        o = jax.lax.map(lambda a: block(a[0], kg, vg, a[1]),
                        (qb, jnp.arange(T // blk) * blk))
        return jnp.moveaxis(o, 0, 1).reshape(qg.shape)

    o = jax.lax.map(group, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2).reshape(B, T, qh * hd) @ p["o"]["kernel"]


def _experts_layer(p, u):
    """Σ_k w_k E_ik(u) over the experts HELD, each applied to every
    token and weighted by zero where it was not selected; no shared
    expert → (the layer's output, its assignments to every expert)."""
    B, T, d = u.shape
    first, held, _ = _held()
    x = u.reshape(B * T, d)
    experts, weights, counts = _km._route(p, x)
    dense_w = jnp.sum(
        jnp.where(experts[..., None] == first + jnp.arange(held),
                  weights[..., None], 0.0), axis=1).astype(x.dtype)

    @jax.checkpoint
    def one(acc, ew):
        w_in, w_out, w = ew
        return acc + _km._gated(x, w_in, w_out) * w[:, None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_in"], p["experts_out"], dense_w.T))
    return routed.reshape(B, T, d), counts


@jax.checkpoint
def _block(p, h):
    mixer = _short_conv if "conv_kernel" in p["mixer"] else _attention
    h = h + mixer(p["mixer"], _rms_norm(p["norm1"], h))
    u = _rms_norm(p["norm2"], h)
    if "moe" in p:
        out, counts = _experts_layer(p["moe"], u)
        return h + out, counts
    return h + _km._gated(u, p["mlp_in"]["kernel"],
                          p["mlp_out"]["kernel"]), None


def _forward(params, x):
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    counts = []
    for i in range(CFG["num_hidden_layers"]):
        h, c = _block(params[f"layer{i}"], h)
        counts += [] if c is None else [c]
    h = _rms_norm(params["norm_f"], h)
    return h @ params["head"]["kernel"] + params["head"]["bias"], counts


# -------------------------------------------------------------- adapter
def hybrid_config(cfg: dict):
    """The program's `HybridConfig` of a configuration file."""
    from iotml.models.hybrid import HybridConfig

    if "short_conv_width" not in {f.name for f in
                                  dataclasses.fields(HybridConfig)}:
        raise SystemExit(
            "this checkout's program has no gated short convolution, no "
            "rotary or normed grouped attention and no expert layer without "
            "a shared expert (iotml/models/hybrid.py): it cannot run "
            "sensorformer-lfm2-24b-a2b")
    use(cfg)
    first, held, routed = _held()
    head = _head_dim()
    return HybridConfig(
        d_model=cfg["hidden_size"],
        layer_types=tuple(MIXERS[m] for m in _mixers()),
        ffn_types=_ffn_kinds(),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head,
        attention_multiplier=head ** -0.5, qk_norm=True,
        attn_rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        short_conv_width=cfg["conv_L_cache"],
        mlp_dim=cfg["intermediate_size"], eps=cfg["norm_eps"],
        experts=routed, experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"], shared_dim=0,
        routed_scale=float(cfg["routed_scaling_factor"]),
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0)


# the sparse-expert file's loss, fit, weights' maker and adapter, around
# this file's block and this source's key for the experts held
_km._init, _km._forward, _km.hybrid_config, _km._held = \
    _init, _forward, hybrid_config, _held
init_params, forward, loss_fn, make_fit = \
    _km.init_params, _km.forward, _km.loss_fn, _km.make_fit
normalizer, Trainer = _km.normalizer, _km.Trainer
