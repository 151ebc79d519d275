"""sensorformer-kimi-linear-48b-a3b: the plain reference, and the
adapter that runs the fit leg of `run_streaming_app`'s train mode
(cli/_app.py) as `cli/lstm.py` instantiates it, with the program's
`SensorHybrid` at the source's widths as `make_model`, job after job.

The reference: Kimi Linear's layer equations as the source's
`config.json` and the catalog's description state them (the
configuration's file has them in words), in `jax.numpy`.  A block is
`h' = h + mixer(RMSNorm(h))`, `h'' = h' + ffn(RMSNorm(h'))`.

- a KDA mixer (`linear_attn_config.kda_layers`), WRITTEN OUT here and
  STEPPED: `[q̃, k̃, ṽ] = u W_qkv`; each channel through its causal
  convolution of four taps as FOUR SHIFTED MULTIPLY-ADDS (zeros before
  the window's first position, no bias) and SiLU; q and k divided by
  their L2 norm a head (ε 1e-6 inside the root), q scaled by 128^-½;
  `g = −exp(A_log) · softplus((u W_f↓) W_f↑ + dt_bias)`,
  `β = sigmoid(u W_β)`; then the recurrence one position at a time from
  `S_0 = 0`, exactly as it reads,

      S ← Diag(exp(g_t)) S;  S ← S + β_t k_t (v_t − Sᵀ k_t)ᵀ;  o_t = Sᵀ q_t

  (`(I − β k kᵀ) Diag(α) S + β k vᵀ` with the bracket multiplied out) —
  a `lax.scan` over T in checkpointed segments, one window at a time:
  no chunk, no inverse, nothing of the algorithm the program runs; and
  `(RMSNorm_head(o) ⊙ sigmoid((u W_g↓) W_g↑)) W_o`;
- latent attention (`full_attn_layers`) WITHOUT positions
  (`mla_use_nope`): the sparse-expert file's plain latent attention — a
  head and 1,024 queries at a time — with its rotary turn replaced by
  the identity on both 64-wide parts;
- the gated-SiLU MLP in the leading dense layer, and the expert layer
  with EVERY EXPERT HELD APPLIED DENSELY TO EVERY TOKEN under a weight
  that is zero where it was not selected, plus the shared expert: the
  sparse-expert file's, at these numbers.

Which eight of a layer's 256 experts this chip holds is a PLACEMENT, as
the global-and-window file makes its own (`_balanced_share`, its
`Trainer` that reads the stream's first batch, its `init_params`): each
expert layer's router outputs relabelled so that experts 0-7 are a
balanced share of the load on that batch — here on what the router of
THIS block reads, `RMSNorm(h')`, and only in the layers that route.

What is the same mathematics is imported, not written again: the
weight-only RMSNorm, the sigmoid router, the experts, the masked loss,
Adam, the fit and the adapter (`sensorformer-kimi-vl-a3b-instruct.py`,
through `sensorformer-smallthinker-21b-a3b.py`'s instance of it).
Imports nothing of the program but in the adapter.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

# the placed sparse-expert reference and adapter — an instance of its
# own, so that the block set on it below is this file's alone
_spec = importlib.util.spec_from_file_location(
    "bench_sensorformer_smallthinker_for_kimi_linear", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-smallthinker-21b-a3b.py"))
_st = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_st)
_km = _st._km
CFG = _km.CFG     # this run's configuration file, set by use()
STD = _km.STD     # seeded kernels' deviation
_rms_norm = _km._rms_norm             # weight-only, eps `rms_norm_eps`
_turned = _km._rotary                 # the turn this model leaves out
SEGMENT = 64                          # positions a checkpointed segment


def use(cfg: dict) -> None:
    """The sizes this run's configuration file states; the experts a
    token and the shared experts also under the keys the imported
    reference reads."""
    _km.use(dict(cfg, num_experts_per_tok=cfg["num_experts_per_token"],
                 n_shared_experts=cfg["num_shared_experts"]))


def _held() -> tuple:
    """(first, count, routed over): the experts held here of all."""
    return (CFG["experts_held"]["first"], CFG["num_experts"],
            CFG["published"]["num_experts"])


def _kinds() -> tuple:
    """`kda` or `mla` a layer held, by the source's two lists (layers
    counted from 1)."""
    lin = CFG["linear_attn_config"]
    kinds = {**dict.fromkeys(lin["kda_layers"], "kda"),
             **dict.fromkeys(lin["full_attn_layers"], "mla")}
    return tuple(kinds[i + 1] for i in range(CFG["num_hidden_layers"]))


def _kda_sizes() -> tuple:
    """(heads, a head's width — the two gates' rank too —, taps)."""
    lin = CFG["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


# ------------------------------------------------------------ reference
def _init(key):
    d, f = CFG["hidden_size"], CFG["model"]["features"]
    h = CFG["num_attention_heads"]
    nope, rope = CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"]
    rank, dv = CFG["kv_lora_rank"], CFG["v_head_dim"]
    e = CFG["moe_intermediate_size"]
    shared = CFG["num_shared_experts"] * e
    heads, width, taps = _kda_sizes()
    inner = heads * width
    _, held, routed = _held()
    kinds, ffns = _kinds(), _km._ffn_kinds()
    keys = iter(jax.random.split(key, 16 * len(kinds) + 2))

    def normal(*shape):
        return STD * jax.random.normal(next(keys), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm(size):
        return {"scale": jnp.ones((size,), jnp.float32)}

    def kda():
        dt = jnp.exp(jax.random.uniform(next(keys), (inner,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {"qkv": kernel(d, 3 * inner),
                "conv_kernel": normal(taps, 3 * inner),
                "gates_in": kernel(d, 2 * width + heads),
                "f_up": kernel(width, inner),
                "g_up": kernel(width, inner),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (heads,), jnp.float32, 1.0, 16.0)),
                # softplus(dt_bias) = dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": norm(width), "o": kernel(inner, d)}

    def mla():
        return {"q": kernel(d, h * (nope + rope)),
                "kv_a": kernel(d, rank + rope), "kv_norm": norm(rank),
                "kv_b": kernel(rank, h * (nope + dv)), "o": kernel(h * dv, d)}

    # the tree the program's flax module builds (models/hybrid.py)
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(d)}
    for i, (kind, ffn) in enumerate(zip(kinds, ffns)):
        layer = {"norm1": norm(d), "norm2": norm(d),
                 "mixer": kda() if kind == "kda" else mla()}
        if ffn == "moe_ffn":
            layer["moe"] = {
                "router": normal(d, routed),
                "router_bias": jnp.zeros((routed,), jnp.float32),
                "experts_in": normal(held, d, 2 * e),
                "experts_out": normal(held, e, d),
                "shared_in": kernel(d, 2 * shared),
                "shared_out": kernel(shared, d)}
        else:
            layer["mlp_in"] = kernel(d, 2 * CFG["intermediate_size"])
            layer["mlp_out"] = kernel(CFG["intermediate_size"], d)
        out[f"layer{i}"] = layer
    return out


def _conv_silu(x, kernel):
    """silu(Σ_j kernel[j] · x_{t−(K−1)+j}) a channel, x_t = 0 for t < 0:
    K shifted multiply-adds.  x [T, C], kernel [K, C]."""
    K, T = kernel.shape[0], x.shape[0]
    y = x * kernel[K - 1]
    for back in range(1, K):
        y = y + jnp.pad(x, ((back, 0), (0, 0)))[:T] * kernel[K - 1 - back]
    return jax.nn.silu(y)


def _delta_rule(q, k, v, g, beta):
    """The recurrence, one position at a time from S_0 = 0: q, k, v, g
    [T, H, D], beta [T, H] → o [T, H, D]."""
    T, H, D = k.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, :, None] * S                      # Diag(α_t) S
        held = jnp.sum(k_t[:, :, None] * S, axis=1)           # Sᵀ k_t
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - held)[:, None, :]
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)        # Sᵀ q_t

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(step, S, xs)

    seg = max(s for s in range(1, SEGMENT + 1) if T % s == 0)
    _, o = jax.lax.scan(
        segment, jnp.zeros((H, D, D), jnp.float32),
        tuple(a.reshape((T // seg, seg) + a.shape[1:])
              for a in (q, k, v, g, beta)))
    return o.reshape(T, H, D)


def _kda(p, u):
    heads, width, _ = _kda_sizes()
    inner = heads * width

    @jax.checkpoint
    def window(u):                                            # [T, d]
        T = u.shape[0]
        q, k, v = jnp.split(
            _conv_silu(u @ p["qkv"]["kernel"], p["conv_kernel"]), 3, axis=-1)
        q, k, v = (a.reshape(T, heads, width) for a in (q, k, v))
        q, k = (a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                for a in (q, k))
        q = q * width ** -0.5
        f, gate, beta = jnp.split(u @ p["gates_in"]["kernel"],
                                  [width, 2 * width], axis=-1)
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            f @ p["f_up"]["kernel"] + p["dt_bias"]).reshape(T, heads, width)
        o = _delta_rule(q, k, v, g, jax.nn.sigmoid(beta))
        # the norm over a head's features, ONE weight for all the heads
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + CFG["rms_norm_eps"]) * p["norm"]["scale"]
        gate = jax.nn.sigmoid(gate @ p["g_up"]["kernel"])
        return (o.reshape(T, inner) * gate) @ p["o"]["kernel"]

    return jax.lax.map(window, u)


def _block(kind, p, h):
    @jax.checkpoint
    def block(p, h):
        mixer = _kda if kind == "kda" else _km._attention
        h = h + mixer(p["mixer"], _rms_norm(p["norm1"], h))
        out, counts = _km._ffn(p, _rms_norm(p["norm2"], h))
        return h + out, counts
    return block(p, h)


def _forward(params, x):
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    counts = []
    for i, kind in enumerate(_kinds()):
        h, c = _block(kind, params[f"layer{i}"], h)
        counts += [] if c is None else [c]
    h = _rms_norm(params["norm_f"], h)
    return h @ params["head"]["kernel"] + params["head"]["bias"], counts


# ------------------------------------------------ the experts' placement
def _place(params: dict, x) -> dict:
    """The seeded weights with each router's outputs relabelled so that
    the experts held here are a balanced share of its load on the window
    `x`: layer by layer on the stream the layers before it, placed, hand
    on; a layer's load is counted on what its router reads, the normed
    stream behind the mixer.  A relabelling alone: every column keeps
    its seeded values, held and absent experts keep their order."""
    first, held, routed = _held()

    mixers = {"kda": _kda, "mla": _km._attention}
    mixed = jax.jit(lambda kind, p, h: h + mixers[kind](
        p["mixer"], _rms_norm(p["norm1"], h)), static_argnums=0)
    ffn = jax.jit(lambda p, h: h + _km._ffn(p, _rms_norm(p["norm2"], h))[0])
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    out = dict(params)
    for i, kind in enumerate(_kinds()):
        p = params[f"layer{i}"]
        h = mixed(kind, p, h)
        if "moe" in p:
            u = _rms_norm(p["norm2"], h)
            counts = _km._route(p["moe"], u.reshape(-1, u.shape[-1]))[2]
            here = _st._balanced_share(jax.device_get(counts),
                                       routed // held)
            absent = np.setdiff1d(np.arange(routed), here)
            order = np.concatenate([absent[:first], here, absent[first:]])
            p = dict(p, moe=dict(
                p["moe"], router=p["moe"]["router"][:, order],
                router_bias=p["moe"]["router_bias"][order]))
        h, out[f"layer{i}"] = ffn(p, h), p
    return out


# -------------------------------------------------------------- adapter
def hybrid_config(cfg: dict):
    """The program's `HybridConfig` of a configuration file."""
    from iotml.models.hybrid import HybridConfig

    fields = {f.name for f in dataclasses.fields(HybridConfig)}
    if not {"kda_heads", "mla_rope"} <= fields:
        raise SystemExit(
            "this checkout's program has no delta-rule mixer (a matrix "
            "state a head under a channel-wise gate: iotml/ops/delta.py) "
            "and no latent attention without positions "
            "(iotml/models/hybrid.py): it cannot run "
            "sensorformer-kimi-linear-48b-a3b")
    use(cfg)
    first, held, routed = _held()
    heads, width, taps = _kda_sizes()
    return HybridConfig(
        d_model=cfg["hidden_size"], layer_types=_kinds(),
        ffn_types=_km._ffn_kinds(),
        num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        mla_rope=not cfg["mla_use_nope"],
        kda_heads=heads, kda_head_dim=width, kda_conv_width=taps,
        kda_chunk=cfg["kda_chunk_size"],
        experts=routed, experts_held=(first, held),
        top_k=cfg["num_experts_per_token"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0)


# the placed file's weights' maker and adapter, the sparse-expert file's
# loss and fit, around this file's block, its key for the experts held,
# its placement and NO rotary turn
_km._init, _km._forward, _km.hybrid_config, _km._held = \
    _init, _forward, hybrid_config, _held
_km._rotary = lambda x: x             # mla_use_nope: nothing is turned
_st._held, _st._place = _held, _place
forward, loss_fn, make_fit = _km.forward, _km.loss_fn, _km.make_fit
normalizer = _km.normalizer


def init_params(seed: int) -> dict:
    """The placed file's seeded weights, handed over on the HOST.  The
    reference's fit asks for 9.7 GB of scratch in one piece at the
    bottom of the chip's memory, and 2 GB of weights left where their
    maker's temporaries had put them stand in its way: the control
    (`benchmark/control.py`), which keeps what this returns through the
    reference's fit with no trainer to release ahead of it, could not
    load it (my chip run, PR 50).  The trainer puts them back."""
    return jax.device_get(_st.init_params(seed))


class Trainer(_st.Trainer):
    def seed_weights(self, params, sample_x):
        super().seed_weights(jax.device_put(params), sample_x)
