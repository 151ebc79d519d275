"""SensorHybrid: a stack of layers whose two parts are data — a mixer
(`layer_types`: `mamba`, Mamba-2's chunked state-space scan; `attention`,
grouped-query attention that may norm and turn its heads;
`window_attention`, the same over a sliding window of the last
`attn_window` keys — a band of the flash kernels' tiles beside the
triangle; `mla`, the latent attention of `models.latent_moe`, with
rotary positions or (`mla_rope` off) without; `short_conv`, LFM2's gated
short convolution; `kda`, Kimi Delta Attention — a matrix state a head
under a channel-wise gate and a delta rule, `ops.delta.kda_scan`, the
second chunked form beside the state-space scan's) and a feed-forward part (`ffn_types`: `dense_ffn`, a
gated-SiLU MLP, or `moe_ffn`, that file's sparse-expert layer; left
empty, every layer is `dense_ffn`) — over long per-car sensor histories.

A layer is IBM Granite 4.0-H's block,

    h ← h + r · mixer(RMSNorm(h))        h ← h + r · ffn(RMSNorm(h))

with the residual multiplier r, weight-only RMSNorm and no bias on any
projection.  Rotary positions are a LAYER's (`rope_layout`: which layers'
grouped attention turns its heads by `attn_rope_theta`; left empty,
every one), and an expert layer's router may read the block's own
input, ahead of the mixer (`router_input`).  Either part may be `none`: the layer is then ONE part alone
and builds the norm and the residual of the part it has.  A block may
norm each part's OUTPUT too (`post_norms`: sandwich norms,
`h + N(part(N(h)))`), and a stack may be a LOOP (`loop_steps` > 1): one
set of layers run that many times a step as a scan over one pass's
program, the final norm closing every pass, one head and one exit gate
reading every pass's output, and the model naming its own objective
(`expected_loss`).  The heads a layer holds may be a share of the
model's (one chip's).  One sensor record is one position: `Dense(features
→ d_model)` in, `Dense(d_model → features)` out.  The recurrent state
starts at zero at the window's start (carrying it on is ROADMAP M2).

Every block is recomputed in the backward pass (`nn.remat`), and what
that recomputation may KEEP from the forward pass is a row of `TABLE`:
the names its part gives the value (`checkpoint_name`; outside a
recomputation a name is the identity), the kind the registry counts it
under, the part of a layer that makes it, and its bytes in one
application of that part.  A row without `inner` is kept always — what a
kernel or the router made: small, and dear to make again.  A row with
`inner` is large, and a byte budget decides in WHICH layers a policy
lists its name (`remat_budget`: a third of what the device's memory
holds beyond a trainer's arrays, the rows kept always and what the
widest mixer's backward holds at once, `backward_bytes`).  The budget
buys what is dearest to remake a byte first (`budget_takes`): a kept
element spares a product over `inner` features, 2 × `inner` operations
by an element's bytes; among equals in the table's order, and within a
name from the last layer down (its value lives shortest) until one does
not fit — which ends that name and not the cheaper ones behind it.  What
each row was measured to buy on the chip is `PERF.md` §6's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..obs import metrics as obs_metrics
from ..ops import moe, rope
from ..ops.delta import kda_scan
from ..ops.ssd import causal_conv1d_fused, causal_conv1d_silu, ssd_scan
from . import latent_moe
from .latent_moe import (ExpertLayer, LatentAttention, causal_attention,
                         gated_mlp)
from .latent_moe import dense as _dense
from .latent_moe import normal as _normal

#: a device's memory where the backend reports no `bytes_limit` (the
#: CPU): a TPU v5e's
DEVICE_BYTES = 16 * 2 ** 30
KINDS = ("mamba", "attention", "mla", "short_conv", "window_attention",
         "kda")
#: the mixers that are `GroupedAttention`: over the whole past, or a window
GROUPED = ("attention", "window_attention")
FFN_KINDS = ("dense_ffn", "moe_ffn")
NONE = "none"   # a layer without that part
#: what a model's own objective reports, beside its layers' collections
#: (`train.loop.make_loss_fn`), and a looped stack's two entries there
OBJECTIVE = "objective"
PASS_LOSS, EXIT_MASS = "pass_loss", "exit_mass"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The stack's widths, under the names the text above uses; small
    defaults for tests, Granite 4.0-H Micro's in `benchmark/configs/`."""

    d_model: int = 64
    layer_types: Tuple[str, ...] = ("mamba", "attention", "mamba")
    num_heads: int = 4
    num_kv_heads: int = 2
    # an attention head's width; 0: d_model // num_heads, every head held
    head_dim: int = 0
    mlp_dim: int = 128
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_state: int = 8
    conv_width: int = 4
    chunk: int = 8
    # a gated short convolution's taps a channel (`short_conv`)
    short_conv_width: int = 3
    # grouped attention (`attention`): a per-head RMSNorm of q and k, and
    # rotary positions over the whole head (0: none)
    qk_norm: bool = False
    attn_rope_theta: float = 0.0
    # which layers' grouped attention turns its heads, a flag a layer
    # (the source's `rope_layout`); (): every one, where theta is set
    rope_layout: Tuple[int, ...] = ()
    # the keys a `window_attention` layer's query meets, its own position
    # among them: t − attn_window < j ≤ t
    attn_window: int = 0
    eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # the feed-forward kind a layer; () is `dense_ffn` in every layer
    ffn_types: Tuple[str, ...] = ()
    # latent attention (`mla`): the latent's rank, and a head's features
    # without positions, with rotary positions, and of its values
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 10000.0
    # whether latent attention turns its `rope_dim`-wide parts at all (a
    # model whose latent layers carry no positions keeps the parts,
    # un-turned)
    mla_rope: bool = True
    # Kimi Delta Attention (`kda`): heads of `kda_head_dim` keys and
    # values each (the rank of its two low-rank gates too), the taps of
    # its three convolutions, and the positions a chunk of its scan holds
    kda_heads: int = 4
    kda_head_dim: int = 16
    kda_conv_width: int = 4
    kda_chunk: int = 8
    # sparse experts (`moe_ffn`): routed over `experts`, `top_k` a
    # token; (first, count) of those held here; an expert's width, and
    # the shared expert's
    experts: int = 8
    experts_held: Tuple[int, int] = (0, 8)
    top_k: int = 2
    expert_dim: int = 32
    shared_dim: int = 32
    routed_scale: float = 1.0
    # the experts' form (`ops.moe.EXPERT_FORMS`), routed and shared, and
    # the latent the routed ones act in (0: at the stream's width)
    expert_form: str = "gated_silu"
    moe_latent: int = 0
    # the router (`ops.moe.ROUTER_FORMS`: the sigmoid form has the
    # selection-only bias, the other no such leaf) and what it reads:
    # `ffn`, the normed stream the experts read, or `block`, the block's
    # own input ahead of the mixer, un-normed
    router_form: str = "sigmoid"
    router_input: str = "ffn"
    # a looped stack: the passes a step makes over its one set of layers
    # (1: none, and no exit gate), whether a block norms each part's
    # OUTPUT ahead of the residual add (sandwich norms), and the weight
    # of the exit distribution's entropy in the expected loss
    loop_steps: int = 1
    post_norms: bool = False
    exit_entropy_weight: float = 0.1

    def ffn_kinds(self) -> Tuple[str, ...]:
        return self.ffn_types or ("dense_ffn",) * len(self.layer_types)

    def attn_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def turns(self, layer: int) -> bool:
        """Whether layer `layer`'s grouped attention turns its heads."""
        return bool(self.attn_rope_theta) and self.layer_types[layer] \
            in GROUPED and bool(not self.rope_layout
                                or self.rope_layout[layer])


class Kept(NamedTuple):
    """A value a block's recomputation may keep: a row of `TABLE`."""

    kind: str                # `iotml_remat_*{kind=}` counts it under this
    names: Tuple[str, ...]   # as its part names it; (): the scan keeps it
    # the parts that make it → (cfg, tokens, itemsize) → its bytes in
    # ONE application of that part
    bytes: dict
    # bought by the budget: (cfg, part) → the width the product that
    # remakes an element contracts over; None: kept always
    inner: Optional[Callable] = None
    kernels: bool = False    # attention's kernels make it: not `dense`
    sandwich: bool = False   # named where a post norm reads it, only


def part_inner(m: HybridConfig, part: str) -> int:
    """The width the product that makes a part's output contracts over
    (what remaking an element of that output costs, in multiply-adds):
    the MLP's `mlp_dim`, an expert layer's shared width and a token's
    routed ones (or the latent they act in), a mixer's heads × their
    (value) width; 0 for `none`."""
    return {"dense_ffn": m.mlp_dim,
            "moe_ffn": m.shared_dim + (m.moe_latent or m.top_k * m.expert_dim),
            "attention": m.num_heads * m.attn_head_dim(),
            "window_attention": m.num_heads * m.attn_head_dim(),
            "mla": m.num_heads * m.v_dim,
            "mamba": m.ssm_heads * m.ssm_head_dim,
            "kda": m.kda_heads * m.kda_head_dim,
            "short_conv": m.d_model}.get(part, 0)


def _stream(m, tokens, size):
    return tokens * m.d_model * size


#: the feed-forward part's first product, as `models.latent_moe
#: .gated_mlp` names it (the dense MLP's and the shared expert's alike),
#: and a sandwich block's two outputs ahead of their post norms
FFN_KEPT = latent_moe.FFN_HIDDEN
FFN_OUT, MIXER_OUT = "ffn_out", "mixer_out"
#: what the passes' scan stacks beside the names, a pass: every block's
#: input and the closing's (`SensorHybrid._kept_bytes`' own term) — in
#: a stack that is no loop a block's input is one of the program's
#: other temporaries, which `remat_budget` leaves room for
_SCAN = Kept("loop_inputs", (), {})
#: every value the recomputation may keep.  Kept always, by the names of
#: `ops.attention._flash_fwd_rule`, `models.latent_moe.LatentAttention`,
#: `ops.moe.route` and `dispatch_plan`, `models.latent_moe.ExpertLayer`;
#: then what the passes' scan stacks beside the names; then what the
#: byte budget buys, in the order equals are taken in
TABLE = (
    # out [B, T, H, Dv] and a float32 lse [B, H, T]
    Kept("flash", ("flash_out", "flash_lse"),
         {**dict.fromkeys(GROUPED, lambda m, tokens, size: tokens
                          * m.num_heads * (m.attn_head_dim() * size + 4)),
          "mla": lambda m, tokens, size: tokens * m.num_heads
          * (m.v_dim * size + 4)}, kernels=True),
    # latent attention's rotated q and assembled k, [B, T, H, nope + rope]
    # (without positions q is not turned, and not kept)
    Kept("latent_qk", ("mla_q", "mla_k"),
         {"mla": lambda m, tokens, size: (1 + m.mla_rope) * tokens
          * m.num_heads * (m.nope_dim + m.rope_dim) * size}),
    # the selection, the selected scores and the plan: a top-k and a sort
    Kept("router", ("route_experts", "route_picked", "dispatch_plan"),
         {"moe_ffn": lambda m, tokens, size: moe.plan_kept_bytes(
             tokens, m.top_k, m.experts_held[1], m.experts)}),
    # the routed sum ahead of a latent's back-projection
    Kept("experts", ("routed_sum",),
         {"moe_ffn": lambda m, tokens, size: tokens * m.moe_latent * size}),
    _SCAN,
    # `mlp_in`'s or `shared_in`'s `[tokens, wide × width]` (experts
    # without a shared one: 0), remade by a product over `d_model`: the
    # activation is made again from it, elementwise, in what reads it
    Kept("ffn", (FFN_KEPT,),
         {"dense_ffn": lambda m, tokens, size: tokens
          * moe.EXPERT_FORMS["gated_silu"] * m.mlp_dim * size,
          "moe_ffn": lambda m, tokens, size: tokens
          * moe.EXPERT_FORMS[m.expert_form] * m.shared_dim * size},
         inner=lambda m, part: m.d_model),
    # the post norm's backward reads the norm's INPUT: kept, the part's
    # last product is not run again for it
    Kept(FFN_OUT, (FFN_OUT,), dict.fromkeys(FFN_KINDS, _stream), part_inner,
         sandwich=True),
    Kept(MIXER_OUT, (MIXER_OUT,), dict.fromkeys(KINDS, _stream), part_inner,
         sandwich=True),
)
#: the names every layer's policy lists
KEPT = tuple(name for row in TABLE if not row.inner for name in row.names)
#: the names the byte budget decides, and each one's kind
BUDGETED = {row.names[0]: row.kind for row in TABLE if row.inner}


def row_bytes(row: Kept, m: HybridConfig, tokens: int, itemsize: int,
              attn_mode: Optional[str] = None) -> tuple:
    """(The part that makes a row's value, its bytes a step) a layer —
    over ALL the stack's passes: a policy is a layer's, it keeps a value
    in all the layer's applications or in none; (`none`, 0) where
    neither part of the layer makes it."""
    passes = m.loop_steps * (attn_mode != "dense" or not row.kernels)
    parts = (next((part for part in layer if part in row.bytes), NONE)
             for layer in zip(m.layer_types, m.ffn_kinds()))
    return tuple((part, passes * row.bytes[part](m, tokens, itemsize)
                  if part != NONE else 0) for part in parts)


class Candidate(NamedTuple):
    """A large value a layer's recomputation keeps if the byte budget
    takes it: the name it goes by, its layer, its bytes a step over all
    the stack's passes, and the operations a kept byte spares."""

    name: str
    layer: int
    bytes: int
    density: float


def budget_candidates(cfg: HybridConfig, tokens: int, itemsize: int) -> tuple:
    """What the byte budget chooses among, in the order equals are taken
    in: the budgeted rows of `TABLE`, a candidate a layer (a layer
    without the part is listed with 0 bytes: no candidate)."""
    return tuple(
        Candidate(row.names[0], layer, size,
                  2 * row.inner(cfg, part) / itemsize)
        for row in TABLE if row.inner and (cfg.post_norms or not row.sandwich)
        for layer, (part, size) in enumerate(
            row_bytes(row, cfg, tokens, itemsize)))


def ffn_hidden_bytes(cfg: HybridConfig, tokens: int, itemsize: int) -> tuple:
    """The bytes a step of each layer's first feed-forward product."""
    return tuple(c.bytes for c in budget_candidates(cfg, tokens, itemsize)
                 if c.name == FFN_KEPT)


def device_bytes() -> int:
    """What one device's memory holds, by the backend's own count."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit", DEVICE_BYTES)


def backward_bytes(m: HybridConfig, tokens: int, itemsize: int) -> int:
    """What the widest mixer's backward pass holds at once where that is
    more than `remat_budget`'s two thirds are there for: a `kda`
    mixer's twelve arrays of `[tokens, heads × width]` — the `qkv`
    product (three wide), its three convolved runs, both gates, and the
    four cotangent stacks the scan's backward hands back (q's, k's, v's
    and the decay gate's), alive together where that backward ends; one
    block's, since the blocks are recomputed one at a time.  0 for the
    other kinds, whose widest blocks the two thirds have covered."""
    return ("kda" in m.layer_types) * 12 * tokens \
        * m.kda_heads * m.kda_head_dim * itemsize


def remat_budget(limit: int, held_bytes: int, kept_bytes: int,
                 backward: int) -> int:
    """The bytes a step the large names may keep on a device of `limit`
    bytes: a third of what a trainer's arrays (`held_bytes`), `KEPT`'s
    names and the widest mixer's backward (`backward_bytes`) leave — the
    program's other temporaries (1.6-3.8 GB in the benchmark's cells)
    and room to spare take the rest."""
    return max(0, limit - held_bytes - kept_bytes - backward) // 3


def kept_layers(candidates, budget: int) -> tuple:
    """The layers whose candidate (bytes a layer, 0: none) is kept:
    from the LAST down while their sum stays within `budget`, in the
    stack's order."""
    kept, total = [], 0
    for layer in reversed(range(len(candidates))):
        if not candidates[layer]:
            continue
        total += candidates[layer]
        if total > budget:
            break
        kept.append(layer)
    return tuple(reversed(kept))


def budget_takes(candidates, budget: int) -> tuple:
    """The candidates `budget` bytes buy: dearest to remake a byte
    first; among equals a name at a time in the order listed, each from
    the last layer down while it fits (`kept_layers`) — a name that
    does not fit ends there, and what is left buys the ones behind it."""
    groups = {}
    for c in candidates:
        groups.setdefault((c.density, c.name), []).append(c)
    taken = []
    # a stable sort by density alone: equals stay in the listed order
    for _, group in sorted(groups.items(), key=lambda g: -g[0][0]):
        fits = [group[i] for i in kept_layers(
            [c.bytes for c in group], budget)]
        budget -= sum(c.bytes for c in fits)
        taken += fits
    return tuple(taken)


def _a_log_init(key, shape, dtype=jnp.float32):
    """a = -exp(A_log) uniform in [-16, -1]: Mamba-2's usual range."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(dt_bias) log-uniform in [1e-3, 1e-1]: steps that keep a
    state alive for tens to thousands of positions."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))   # the inverse of softplus


class MambaMixer(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        m = self.cfg
        B, T, _ = u.shape
        H, P, N = m.ssm_heads, m.ssm_head_dim, m.ssm_state
        inner = H * P
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = jnp.split(
                _dense(2 * inner + 2 * N + H, "in_proj")(u),
                [inner, 2 * inner + 2 * N], axis=-1)
        with jax.named_scope("conv"):
            # out as the scan's three operands: nothing splits it again
            x, b, c = causal_conv1d_silu(
                xbc,
                self.param("conv_kernel", _normal,
                           (m.conv_width, inner + 2 * N)),
                self.param("conv_bias", nn.initializers.zeros,
                           (inner + 2 * N,)),
                splits=(inner, N, N))
        with jax.named_scope("ssd"):
            x = x.reshape(B, T, H, P)
            dt = nn.softplus(dt + self.param("dt_bias", _dt_bias_init, (H,)))
            a = -jnp.exp(self.param("A_log", _a_log_init, (H,)))
            y = ssd_scan(x, dt, a, b, c, m.chunk)
            y = y + x * self.param("D", nn.initializers.ones, (H,))[:, None]
        with jax.named_scope("gate_norm"):
            # one group: the gate first, then the norm over all channels
            y = nn.RMSNorm(epsilon=m.eps, name="norm")(
                y.reshape(B, T, inner) * nn.silu(z))
        with jax.named_scope("ssm_proj"):
            return _dense(m.d_model, "out_proj")(y)


class KdaMixer(nn.Module):
    """Kimi Delta Attention: `[q̃, k̃, ṽ] = u W_qkv`, each run through
    its own causal depthwise convolution of `kda_conv_width` taps (no
    bias) and SiLU — `ops.ssd`'s two kernels, three runs of one array;
    `q`, `k` L2-normed a head (ε 1e-6 inside the root), `q` scaled by
    `kda_head_dim`^-½; the decay a vector a head and position,
    `g = −exp(A_log) · softplus((u W_f↓) W_f↑ + dt_bias)`, `β =
    sigmoid(u W_β)` a head, both gates' rank a head's width; `o =
    kda_scan(q̃, k̃, v, f, β, A_log, dt_bias)` (the norms and the decay's
    softplus are the scan's, a segment at a time); out `(RMSNorm_head(o)
    ⊙ sigmoid((u W_g↓) W_g↑)) W_o`, the norm's one weight shared by the
    heads.  The three thin products of u — the two gates'
    down projections and β's — are one (`gates_in`)."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        m = self.cfg
        B, T, _ = u.shape
        H, D = m.kda_heads, m.kda_head_dim
        inner = H * D
        with jax.named_scope("kda_proj"):
            qkv = _dense(3 * inner, "qkv")(u)
            f, gate, beta = jnp.split(_dense(2 * D + H, "gates_in")(u),
                                      [D, 2 * D], axis=-1)
            f = _dense(inner, "f_up")(f)
            gate = _dense(inner, "g_up")(gate)
        with jax.named_scope("kda_conv"):
            q, k, v = causal_conv1d_fused(
                qkv, self.param("conv_kernel", _normal,
                                (m.kda_conv_width, 3 * inner)),
                splits=(inner,) * 3)
        q, k, v, f = (a.reshape(B, T, H, D) for a in (q, k, v, f))
        o = kda_scan(q, k, v, f, nn.sigmoid(beta),
                     self.param("A_log", _a_log_init, (H,)),
                     self.param("dt_bias", _dt_bias_init,
                                (inner,)).reshape(H, D), m.kda_chunk)
        with jax.named_scope("gate_norm"):
            # the norm a head, one weight for all of them; then the gate
            o = nn.RMSNorm(epsilon=m.eps, name="norm")(o).reshape(
                B, T, inner) * nn.sigmoid(gate)
        with jax.named_scope("kda_proj"):
            return _dense(m.d_model, "o")(o)


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution: `[b, c, x] = u W_in`, `y = conv(b
    ⊙ x)` — a depthwise causal convolution of `short_conv_width` taps a
    channel, no bias, no activation — and `(c ⊙ y) W_out`."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        m = self.cfg
        d = m.d_model
        with jax.named_scope("conv_proj"):
            b, c, x = jnp.split(_dense(3 * d, "in_proj")(u), 3, axis=-1)
        with jax.named_scope("short_conv"):
            # the gates are XLA's multiplies around the kernels' call
            (y,) = causal_conv1d_fused(
                b * x, self.param("conv_kernel", _normal,
                                  (m.short_conv_width, d)),
                activation="none")
            y = c * y
        with jax.named_scope("conv_proj"):
            return _dense(d, "out_proj")(y)


def rotary_tables(m: HybridConfig, attn_mode: str, T: int):
    """`iotml_rope`'s tables for grouped attention's heads over T
    positions — or None where the pair form turns them: no positions,
    `dense` attention (the plain path), or query or key heads that fill
    no whole 128-lane tiles.  `SensorHybrid` makes them once a step,
    outside the passes' loop and the blocks' recomputation, for the
    layers that turn (`HybridConfig.turns`); a layer applied on its own
    makes its own."""
    D = m.attn_head_dim()
    if not m.attn_rope_theta or attn_mode == "dense" or not all(
            rope.lanes(n * D, D) for n in (m.num_heads, m.num_kv_heads)):
        return None
    with jax.named_scope("rope"):
        return rope.tables(T, D, m.attn_rope_theta)


class GroupedAttention(nn.Module):
    """Fewer key/value heads than query heads, a softmax scale of its
    own; `qk_norm`: a weight-only RMSNorm over a head's features, one
    weight vector for all query heads and one for all key heads;
    `attn_rope_theta` (0: none, the state-space layers carry order):
    rotary positions over the whole head, after the norms — in the
    layers that `turn`; `window` (0: the whole causal past): the keys a
    query meets, its own position among them."""

    cfg: HybridConfig
    attn_mode: str   # dense | flash | flash_interpret
    turn: bool = True
    window: int = 0

    @nn.compact
    def __call__(self, u, rope_tables=None):
        m = self.cfg
        B, T, _ = u.shape
        H, G, D = m.num_heads, m.num_kv_heads, m.attn_head_dim()
        theta = m.attn_rope_theta if self.turn else 0.0
        obs_metrics.attn_window.set(self.window)
        q = _dense(H * D, "q")(u).reshape(B, T, H, D)
        k = _dense(G * D, "k")(u).reshape(B, T, G, D)
        v = _dense(G * D, "v")(u).reshape(B, T, G, D)
        obs_metrics.attn_qk_norm.set(int(m.qk_norm))
        obs_metrics.attn_rotary_dim.set(D if theta else 0)
        if m.qk_norm:
            with jax.named_scope("qk_norm"):
                q = nn.RMSNorm(epsilon=m.eps, name="q_norm")(q)
                k = nn.RMSNorm(epsilon=m.eps, name="k_norm")(k)
        if not theta:
            rope_tables = None   # the stack's, for the layers that turn
        elif rope_tables is None:
            rope_tables = rotary_tables(m, self.attn_mode, T)
        obs_metrics.attn_rotary_kernel.set(2 * (rope_tables is not None))
        if theta:
            # in the flash kernels' own layout where they run and the
            # lanes allow, else as XLA's pair form
            with jax.named_scope("rope"):
                if rope_tables is None:
                    q, k = (moe.rotary(a, m.attn_rope_theta) for a in (q, k))
                else:
                    q, k = (rope.rope(
                        a, rope_tables,
                        interpret=self.attn_mode == "flash_interpret")
                        for a in (q, k))
        o = causal_attention(q, k, v, self.attn_mode, m.attention_multiplier,
                             self.window or None)
        return _dense(m.d_model, "o")(o.reshape(B, T, H * D))


class HybridBlock(nn.Module):
    kind: str
    cfg: HybridConfig
    attn_mode: str
    ffn: str = "dense_ffn"
    turn: bool = True   # grouped attention's heads take rotary positions

    @nn.compact
    def __call__(self, h, rope_tables=None):
        m = self.cfg
        obs_metrics.model_post_norms.set(
            m.post_norms * ((self.kind != NONE) + (self.ffn != NONE)))
        plan = None
        if self.ffn == "moe_ffn" and m.router_input == "block":
            # the router reads the block's own input, ahead of the mixer
            # and un-normed: its plan waits for the experts behind it
            experts = ExpertLayer(m, self.attn_mode, name="moe")
            plan = experts(h, plan_only=True)
        if self.kind != NONE:
            u = nn.RMSNorm(epsilon=m.eps, name="norm1")(h)
            if self.kind == "mamba":
                mixed = MambaMixer(m, name="mixer")(u)
            elif self.kind == "short_conv":
                mixed = ShortConvMixer(m, name="mixer")(u)
            elif self.kind == "kda":
                mixed = KdaMixer(m, name="mixer")(u)
            else:
                with jax.named_scope("attn"):
                    if self.kind == "mla":
                        mixed = LatentAttention(m, self.attn_mode,
                                                name="mixer")(u)
                    else:
                        mixed = GroupedAttention(
                            m, self.attn_mode, self.turn,
                            (self.kind == "window_attention")
                            * m.attn_window, name="mixer")(u, rope_tables)
            h = h + m.residual_multiplier * self._post_norm(mixed, 1)
        if self.ffn == NONE:
            return h
        u = nn.RMSNorm(epsilon=m.eps, name="norm2")(h)
        if plan is not None:
            out = experts(u, plan)
        elif self.ffn == "moe_ffn":
            out = ExpertLayer(m, self.attn_mode, name="moe")(u)
        else:
            with jax.named_scope("mlp"):
                out = gated_mlp(u, m.mlp_dim, m.d_model)
        return h + m.residual_multiplier * self._post_norm(out, 2)

    def _post_norm(self, part, which: int):
        """A sandwich block norms a part's output too, ahead of the
        residual add: `post_norm1` the mixer's, `post_norm2` the
        feed-forward part's."""
        if not self.cfg.post_norms:
            return part
        # the norm's backward reads its input: by a name, a layer whose
        # policy lists it does not run the part's last product again
        part = checkpoint_name(part, (MIXER_OUT, FFN_OUT)[which - 1])
        with jax.named_scope("post_norm"):
            return nn.RMSNorm(epsilon=self.cfg.eps,
                              name=f"post_norm{which}")(part)


class SensorHybrid(nn.Module):
    """Next-record prediction over [B, T, features]."""

    cfg: HybridConfig = HybridConfig()
    features: int = 18
    attn_mode: str = "dense"

    @property
    def report_collections(self) -> Tuple[str, ...]:
        """The variable collections this model's layers report data in
        (`Trainer` reads them back with the losses); none without an
        expert layer.  A model with neither this nor an `objective`
        reports nothing, and its fit is the program it always was."""
        return (latent_moe.REPORTS,) if "moe_ffn" in self.cfg.ffn_kinds() \
            else ()

    @property
    def objective(self):
        """A looped stack's own loss over `(outputs, y, mask)` — the
        expectation of the passes' losses under the exit distribution,
        less its entropy (`expected_loss`) — which `train.loop
        .make_loss_fn` takes in place of the masked mean squared error
        of one output; None where the stack makes one pass."""
        if self.cfg.loop_steps == 1:
            return None
        return functools.partial(expected_loss,
                                 beta=self.cfg.exit_entropy_weight)

    def record_reports(self, reports) -> None:
        """What a fit's reports say, into the registry: the expert
        layers' collection and, from a looped stack's objective, the
        passes' losses and exit masses, `[epochs, batches, passes]`."""
        latent_moe.record_reports(self.cfg,
                                  reports.get(latent_moe.REPORTS, {}))
        said = reports.get(OBJECTIVE, {})
        for name, gauge in ((PASS_LOSS, obs_metrics.loop_pass_loss),
                            (EXIT_MASS, obs_metrics.loop_exit_mass)):
            if name in said:
                means = np.asarray(said[name], np.float64).reshape(
                    -1, self.cfg.loop_steps).mean(axis=0)
                for t, value in enumerate(means):
                    gauge.set(float(value), kind=f"pass{t + 1}")

    def _kept_bytes(self, x) -> dict:
        """kind → the bytes a step of x [B, T, features] the blocks keep
        whatever the budget (`TABLE`'s rows without `inner`)."""
        m, tokens, size = self.cfg, x.shape[0] * x.shape[1], x.dtype.itemsize
        kept = {row.kind: sum(b for _, b in row_bytes(
                    row, m, tokens, size, self.attn_mode))
                for row in TABLE if not row.inner}
        kept[_SCAN.kind] = (m.loop_steps > 1) * m.loop_steps \
            * (len(m.layer_types) + 1) * _stream(m, tokens, size)
        return kept

    @nn.compact
    def __call__(self, x):
        """→ the next record's prediction at every position
        `[B, T, features]`; from a looped stack `(predictions
        [passes, B, T, features], exit-gate logits [passes, B, T])`,
        every pass's through the one head and the one gate."""
        m = self.cfg
        ffns = m.ffn_kinds()
        unknown = (set(m.layer_types) - set(KINDS + (NONE,))) \
            | (set(ffns) - set(FFN_KINDS + (NONE,)))
        if unknown or len(ffns) != len(m.layer_types) \
                or (NONE, NONE) in zip(m.layer_types, ffns) \
                or len(m.rope_layout) not in (0, len(m.layer_types)) \
                or m.router_input not in latent_moe.ROUTER_INPUTS \
                or ("window_attention" in m.layer_types
                    and m.attn_window < 1):
            raise ValueError(
                f"layer_types {m.layer_types} and ffn_types {m.ffn_types}: "
                f"known kinds are {KINDS} and {FFN_KINDS}, one of each a "
                f"layer, of which one may be {NONE!r}; rope_layout "
                f"{m.rope_layout} is a flag a layer or empty, router_input "
                f"{m.router_input!r} one of {latent_moe.ROUTER_INPUTS}, and "
                f"a window_attention layer needs attn_window "
                f"{m.attn_window} >= 1")
        if m.loop_steps < 1:
            raise ValueError(f"loop_steps {m.loop_steps}: a stack makes at "
                             f"least one pass over its layers")
        # what engaged, at trace time (as the flash geometry is said)
        for kind in KINDS:
            obs_metrics.model_layers.set(m.layer_types.count(kind),
                                         kind=kind)
        for kind in FFN_KINDS:
            obs_metrics.model_layers.set(ffns.count(kind), kind=kind)
        obs_metrics.model_loop_steps.set(m.loop_steps)
        obs_metrics.remat_blocks.set(len(m.layer_types))
        kept = self._kept_bytes(x)
        # what a trainer holds beside the fit's temporaries: the
        # parameters, Adam's two moments, and at its start a second copy
        # of the parameters (the seeded or restored weights the state is
        # built from) — and under a loop every gradient, whole only when
        # the backward of the FIRST pass ends, where a leaf used once a
        # step is updated and dropped as its gradient arrives; no
        # parameters yet while they are made, and nothing is recomputed
        # then
        held = (4 + (m.loop_steps > 1)) * sum(
            p.size * p.dtype.itemsize for p in jax.tree.leaves(
                self.variables.get("params", {})))
        tokens, size = x.shape[0] * x.shape[1], x.dtype.itemsize
        candidates = budget_candidates(m, tokens, size)
        taken = budget_takes(candidates, remat_budget(
            device_bytes(), held, sum(kept.values()),
            backward_bytes(m, tokens, size)))
        for name, kind in BUDGETED.items():
            kept[kind] = sum(c.bytes for c in taken if c.name == name)
            obs_metrics.remat_kept_layers.set(
                sum(c.name == name for c in taken), kind=kind)
            obs_metrics.remat_keepable_layers.set(
                sum(c.name == name and c.bytes > 0 for c in candidates),
                kind=kind)
        for kind, size in kept.items():
            obs_metrics.remat_kept_bytes.set(size, kind=kind)
        h = m.embedding_multiplier * nn.Dense(
            m.d_model, kernel_init=_normal, name="embed")(x)
        # a layer's policy: `KEPT` and what the budget took in it; one
        # recomputed block a distinct set
        keeps = [tuple(c.name for c in taken if c.layer == layer)
                 for layer in range(len(m.layer_types))]
        names = jax.checkpoint_policies.save_only_these_names
        block = {bought: nn.remat(HybridBlock, policy=names(*KEPT, *bought))
                 for bought in dict.fromkeys(keeps)}

        # grouped attention's rotary tables, once a step for every layer,
        # pass and recomputation (None: no such layer, or the pair form)
        turn = [m.turns(i) for i in range(len(m.layer_types))]
        tables = rotary_tables(m, self.attn_mode, x.shape[1]) \
            if any(turn) else None

        def layers(stack, h, tables):
            # the modules are `stack`'s: this model's, or its stand-in
            # under a lifted loop
            for i, (kind, ffn) in enumerate(zip(m.layer_types, ffns)):
                h = block[keeps[i]](kind, m, stack.attn_mode, ffn, turn[i],
                                    name=f"layer{i}")(h, tables)
            return h

        def closing(stack, h):
            """The final norm, which closes a pass, and the head."""
            h = nn.RMSNorm(epsilon=m.eps, name="norm_f")(h)
            return h, nn.Dense(stack.features, kernel_init=_normal,
                               name="head")(h) / m.logits_scaling

        if m.loop_steps == 1:
            return closing(self, layers(self, h, tables))[1]

        def gated_closing(stack, h):
            h, pred = closing(stack, h)
            with jax.named_scope("exit_gate"):
                gate = nn.Dense(1, kernel_init=_normal, name="exit_gate")(h)
            return h, (pred, gate[..., 0])

        def looped_pass(stack, h, tables):
            # what a pass leaves is what the next one starts from; its
            # closing is recomputed too: kept, the norm's four values of
            # the stream's size would be stacked a pass for one input
            return nn.remat(gated_closing)(stack, layers(stack, h, tables))

        # ONE set of parameters, `loop_steps` passes: a scan whose body
        # is one pass — the program of an L-layer stack; what the blocks
        # keep by name comes back stacked a pass, and a shared leaf's
        # gradient is the backward scan's carry
        _, outputs = nn.scan(
            looped_pass, variable_broadcast="params",
            variable_axes={latent_moe.REPORTS: 0},
            split_rngs={"params": False}, in_axes=nn.broadcast,
            length=m.loop_steps)(self, h, tables)
        return outputs


def exit_log_probs(gates):
    """log p of the exit distribution a position: from the gates' logits
    `[passes, …]` with λ = sigmoid, `p_t = λ_t ∏_{j<t} (1 − λ_j)` and
    the last pass takes what is left, `p_R = ∏_{j<R} (1 − λ_j)`."""
    stay = jax.nn.log_sigmoid(-gates)
    before = jnp.cumsum(stay, axis=0) - stay     # Σ_{j<t} log(1 − λ_j)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(gates[:-1]) + before[:-1], before[-1:]])


def expected_loss(outputs, y, mask, beta: float):
    """A looped stack's objective: the masked mean over windows and
    positions of `Σ_t p_t ℓ_t − β H(p)`, ℓ_t pass t's squared error
    against the target (a mean over the fields), p the exit
    distribution and H its entropy → (loss, the last pass's prediction,
    {the passes' mean losses, the passes' mean exit masses})."""
    preds, gates = outputs                        # [R, B, T, F], [R, B, T]
    per_pass = jnp.mean(jnp.square(preds - y), axis=-1)
    log_p = exit_log_probs(gates.astype(jnp.float32))
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    m = mask[:, None]
    denom = jnp.maximum(jnp.sum(m) * per_pass.shape[-1], 1.0)

    def mean(v):   # over the valid windows' positions
        return jnp.sum(v * m, axis=(-2, -1)) / denom

    loss = mean(jnp.sum(p * per_pass, axis=0) - beta * entropy)
    return loss, preds[-1], {PASS_LOSS: mean(per_pass), EXIT_MASS: mean(p)}
