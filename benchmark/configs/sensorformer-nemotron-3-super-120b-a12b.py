"""sensorformer-nemotron-3-super-120b-a12b: the plain reference, and the
adapter that runs the fit leg of `run_streaming_app`'s train mode
(cli/_app.py) as `cli/lstm.py` instantiates it, with the program's
`SensorHybrid` at the source's widths as `make_model`, job after job.

The reference: Nemotron-H's layer equations as the source's
`config.json` states them (the configuration's file has them in words),
in `jax.numpy`.  Every layer is ONE part, `h + part(RMSNorm(h))`, by the
pattern's letter:

- `M`, the Mamba-2 mixer with GROUPED B and C: heads of group g read
  B_g and C_g and are normed (after the gate) over group g's channels
  apart.  The recurrence is the hybrid configuration's, stepped position
  by position in checkpointed segments, a group at a time — nothing of
  the chunked algorithm the program runs;
- `*`, grouped-query attention without positions, one key/value group
  and 1,024 queries at a time so that T = 8,192 fits;
- `E`, LatentMoE: the sparse-expert configuration's sigmoid router over
  the full-width stream, the routed experts in a latent (`u W_down`,
  EVERY EXPERT HELD APPLIED DENSELY TO EVERY TOKEN and weighted by a
  routing weight that is zero where it was not selected — no sort, no
  tiles — the sum through `W_back`), non-gated squared-ReLU experts, and
  the shared expert at full width.

A chip's share is given to the reference as it is to the program: the
file's counts of heads, groups and experts are those held here, and the
same functions compute the uncut layer when handed the published
counts (`tests/test_nemotron_stack.py` adds the shares up to it).

What is the same mathematics is imported, not written again: the
recurrence (`sensorformer-granite-4.0-h-micro.py`), the router, the
masked loss, Adam and the fit, and the adapter
(`sensorformer-kimi-vl-a3b-instruct.py`).  That file's fit and adapter
are around ITS block; this file hands its own instance of that module
this block (`_init`, `_forward`, `hybrid_config`: the three names set on
`_km` below) and takes the rest as it stands.  Imports nothing of the
program but in the adapter.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp

# the sparse-expert configuration's reference and adapter — an instance
# of its own, so that the block set on it below is this file's alone
_spec = importlib.util.spec_from_file_location(
    "bench_sensorformer_kimi_for_nemotron", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-kimi-vl-a3b-instruct.py"))
_km = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_km)
_recurrence = _km._gh._recurrence
CFG = _km.CFG     # this run's configuration file, set by use()
STD, Q_BLOCK = _km.STD, _km.Q_BLOCK   # seeded kernels' deviation; queries
#                                       a block of the plain attention
PARTS = {"M": ("mamba", "none"), "*": ("attention", "none"),
         "E": ("none", "moe_ffn")}

use = _km.use


def _pattern() -> str:
    return CFG["hybrid_override_pattern"][:CFG["num_hidden_layers"]]


def _mamba_sizes() -> tuple:
    """(heads, head width, state, groups, inner width) as held here."""
    h, p = CFG["mamba_num_heads"], CFG["mamba_head_dim"]
    if h % CFG["n_groups"]:
        raise ValueError("the state heads do not divide into the groups")
    return h, p, CFG["ssm_state_size"], CFG["n_groups"], h * p


# ------------------------------------------------------------ reference
def _init(key):
    d, f = CFG["hidden_size"], CFG["model"]["features"]
    hs, _, n, groups, inner = _mamba_sizes()
    qh, kvh, hd = CFG["num_attention_heads"], CFG["num_key_value_heads"], \
        CFG["head_dim"]
    latent, e = CFG["moe_latent_size"], CFG["moe_intermediate_size"]
    shared = CFG["n_shared_experts"] \
        * CFG["moe_shared_expert_intermediate_size"]
    _, held, routed = _km._held()
    pattern = _pattern()
    keys = iter(jax.random.split(key, 12 * len(pattern) + 2))

    def normal(*shape):
        return STD * jax.random.normal(next(keys), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm(width):
        return {"scale": jnp.ones((width,), jnp.float32)}

    def mamba():
        conv = inner + 2 * groups * n
        dt = jnp.exp(jax.random.uniform(next(keys), (hs,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {"in_proj": kernel(d, 2 * inner + 2 * groups * n + hs),
                "conv_kernel": normal(CFG["conv_kernel"], conv),
                "conv_bias": jnp.zeros((conv,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (hs,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((hs,), jnp.float32),
                "norm": norm(inner), "out_proj": kernel(inner, d)}

    def attention():
        return {"q": kernel(d, qh * hd), "k": kernel(d, kvh * hd),
                "v": kernel(d, kvh * hd), "o": kernel(qh * hd, d)}

    def experts():
        return {"router": normal(d, routed), "router_bias": normal(routed),
                "latent_in": kernel(d, latent),
                "latent_out": kernel(latent, d),
                "experts_in": normal(held, latent, e),
                "experts_out": normal(held, e, latent),
                "shared_in": kernel(d, shared),
                "shared_out": kernel(shared, d)}

    # the tree the program's flax module builds (models/hybrid.py): a
    # layer holds the norm of the part it has and no other
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(d)}
    for i, letter in enumerate(pattern):
        out[f"layer{i}"] = {
            "M": lambda: {"norm1": norm(d), "mixer": mamba()},
            "*": lambda: {"norm1": norm(d), "mixer": attention()},
            "E": lambda: {"norm2": norm(d), "moe": experts()}}[letter]()
    return out


def _rms_norm(p, x):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + jnp.asarray(CFG["norm_eps"], x.dtype)) \
        * p["scale"]


def _mamba(p, u):
    """in_proj's columns are [z, x, B_0…B_G-1, C_0…C_G-1, dt]; heads
    g·H/G … (g+1)·H/G − 1 read group g's B and C, and the gated result is
    normed over each group's channels apart."""
    B, T, _ = u.shape
    h, hp, n, groups, inner = _mamba_sizes()
    k_conv = CFG["conv_kernel"]
    z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * groups * n], axis=-1)
    xbc = jax.lax.conv_general_dilated(
        xbc, p["conv_kernel"][:, None, :], window_strides=(1,),
        padding=[(k_conv - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=inner + 2 * groups * n) + p["conv_bias"]
    x, b, c = jnp.split(jax.nn.silu(xbc), [inner, inner + groups * n],
                        axis=-1)
    x = x.reshape(B, T, groups, h // groups, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(B, T, groups, -1)
    a = -jnp.exp(p["A_log"]).reshape(groups, -1)
    b, c = b.reshape(B, T, groups, n), c.reshape(B, T, groups, n)
    y = jnp.stack([_recurrence(x[:, :, g], dt[:, :, g], a[g], b[:, :, g],
                               c[:, :, g]) for g in range(groups)], axis=2)
    y = y + p["D"].reshape(groups, -1)[..., None] * x
    gated = (y.reshape(B, T, inner) * jax.nn.silu(z)).reshape(
        B, T, groups, inner // groups)
    ms = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    y = (gated * jax.lax.rsqrt(ms + jnp.asarray(CFG["norm_eps"], y.dtype))
         ).reshape(B, T, inner) * p["norm"]["scale"]
    return y @ p["out_proj"]["kernel"]


def _attention(p, u):
    """Query head i reads key/value head i // (heads / groups); a group
    and a block of queries at a time; no positions."""
    B, T, _ = u.shape
    qh, g, hd = CFG["num_attention_heads"], CFG["num_key_value_heads"], \
        CFG["head_dim"]
    q = (u @ p["q"]["kernel"]).reshape(B, T, g, qh // g, hd)
    k = (u @ p["k"]["kernel"]).reshape(B, T, g, hd)
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


def _relu2(x, w_in, w_out):
    return jnp.square(jax.nn.relu(x @ w_in)) @ w_out


def _latent_moe(p, u):
    """(Σ_k w_k E_ik(u W_down)) W_back + S(u) over the experts HELD, each
    applied to every token's latent and weighted by zero where it was not
    selected → (the layer's output, its assignments to every expert)."""
    B, T, d = u.shape
    first, held, _ = _km._held()
    x = u.reshape(B * T, d)
    experts, weights, counts = _km._route(p, x)
    dense_w = jnp.sum(
        jnp.where(experts[..., None] == first + jnp.arange(held),
                  weights[..., None], 0.0), axis=1).astype(x.dtype)
    z = x @ p["latent_in"]["kernel"]

    @jax.checkpoint
    def one(acc, ew):
        w_in, w_out, w = ew
        return acc + _relu2(z, w_in, w_out) * w[:, None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(z),
        (p["experts_in"], p["experts_out"], dense_w.T))
    shared = _relu2(x, p["shared_in"]["kernel"], p["shared_out"]["kernel"])
    return (routed @ p["latent_out"]["kernel"] + shared).reshape(B, T, d), \
        counts


@jax.checkpoint
def _block(p, h):
    """One layer: the part whose parameters it holds."""
    if "moe" in p:
        out, counts = _latent_moe(p["moe"], _rms_norm(p["norm2"], h))
        return h + out, counts
    mixer = _mamba if "in_proj" in p["mixer"] else _attention
    return h + mixer(p["mixer"], _rms_norm(p["norm1"], h)), None


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

    if "moe_latent" not in {f.name for f in dataclasses.fields(HybridConfig)}:
        raise SystemExit(
            "this checkout's program has no one-part layers, no experts in "
            "a latent and no share of the heads (iotml/models/hybrid.py): "
            "it cannot run sensorformer-nemotron-3-super-120b-a12b")
    use(cfg)
    if cfg["n_groups"] != 1:
        raise ValueError("the program's mixer holds one B/C group: a "
                         "chip's share (ops/ssd.py)")
    first, held, routed = _km._held()
    kinds = [PARTS[c] for c in _pattern()]
    return HybridConfig(
        d_model=cfg["hidden_size"],
        layer_types=tuple(k[0] for k in kinds),
        ffn_types=tuple(k[1] for k in kinds),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attention_multiplier=cfg["head_dim"] ** -0.5,
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_state=cfg["ssm_state_size"], conv_width=cfg["conv_kernel"],
        chunk=cfg["chunk_size"], eps=cfg["norm_eps"],
        experts=routed, experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["n_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        expert_form=cfg["mlp_hidden_act"],
        moe_latent=cfg["moe_latent_size"],
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0)


# the sparse-expert file's loss, fit, weights' maker and adapter, around
# this file's block
_km._init, _km._forward, _km.hybrid_config = _init, _forward, hybrid_config
init_params, forward, loss_fn, make_fit = \
    _km.init_params, _km.forward, _km.loss_fn, _km.make_fit
normalizer, Trainer = _km.normalizer, _km.Trainer
