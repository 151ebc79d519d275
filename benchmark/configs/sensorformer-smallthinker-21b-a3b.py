"""sensorformer-smallthinker-21b-a3b: the plain reference, and the
adapter that runs the fit leg of `run_streaming_app`'s train mode
(cli/_app.py) as `cli/lstm.py` instantiates it, with the program's
`SensorHybrid` at the source's widths as `make_model`, job after job.

The reference: SmallThinker's layer equations as the source's
`config.json` and the catalog's description state them (the
configuration's file has them in words), in `jax.numpy`.  For layer l
with input h:

- the router, AHEAD of attention and on the block's own input:
  `s = h W_g` over all 64 (h un-normed), the 6 largest of `s`, and
  `w = softmax(s[E])` over the six selected, WRITTEN OUT here — no
  sigmoid, no bias, no scaling;
- attention on `RMSNorm(h)`: 28 query heads over 4 key/value heads of
  128; where `rope_layout[l]` is 1 rotary positions over the whole head,
  written out (neighbouring pairs, the program's pairing: the
  configuration's file says why that is the family's up to one fixed
  permutation), where it is 0 NO positions; where
  `sliding_window_layout[l]` is 1 the mask is `(j ≤ t) & (j > t −
  sliding_window_size)`, written out over all T keys, where it is 0
  `j ≤ t` alone; one key/value group of one window and 1,024 queries at
  a time so that T = 16,384 fits — no kernel, no tiles, no band;
- the experts on `RMSNorm(h + attention)`: EVERY EXPERT HELD APPLIED
  DENSELY TO EVERY TOKEN, `(relu(u W_gate) ⊙ u W_up) W_down`, weighted by
  a routing weight that is zero where it was not selected — no sort, no
  tiles — and NO shared expert.

A chip's share is given to the reference as it is to the program: the
file's `moe_num_primary_experts` counts the experts held, and the same
functions compute the uncut layer when handed all 64
(`tests/test_smallthinker_stack.py` adds the shares up to it).

Which sixteen a chip holds is a PLACEMENT, made as a deployment's
balancer makes it, from the load it has seen: `init_params` runs this
reference's forward pass on the stream's first batch (the adapter's
trainer reads it ahead of the weights through a cursor of its own),
deals each layer's experts to the chips by their assignments there —
the busiest first, each to the chip with the least load so far — takes
the share nearest the mean, and relabels that router's outputs so that
the share is experts `first..first + held`.  Every value is the seed's;
program and reference are handed the one tree.  Without it the load
this chip holds, and so a job's time, follows the seed by ±8%
(PERF.md section 6, PR 46).

What is the same mathematics is imported, not written again: the
weight-only RMSNorm, the masked loss, Adam, the fit and the adapter
(`sensorformer-kimi-vl-a3b-instruct.py`).  That file's fit and adapter
are around ITS block and ITS names for the experts' counts; this file
hands its own instance of that module this block (`_init`, `_forward`,
`hybrid_config`, `_held`) and the source's count of experts a token
under the name that adapter reads (`use`), and takes the rest as it
stands.  Imports nothing of the program but in the adapter.
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
    "bench_sensorformer_kimi_for_smallthinker", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-kimi-vl-a3b-instruct.py"))
_km = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_km)
CFG = _km.CFG     # this run's configuration file, set by use()
STD, Q_BLOCK = _km.STD, _km.Q_BLOCK   # seeded kernels' deviation; queries
#                                       a block of the plain attention
_rms_norm = _km._rms_norm             # weight-only, eps `rms_norm_eps`


def use(cfg: dict) -> None:
    """The sizes this run's configuration file states; the experts a
    token also under the key the imported adapter's job lines read."""
    _km.use(dict(cfg,
                 num_experts_per_tok=cfg["moe_num_active_primary_experts"]))


def _held() -> tuple:
    """(first, count, routed over): the experts held here of all."""
    return (CFG["experts_held"]["first"], CFG["moe_num_primary_experts"],
            CFG["published"]["moe_num_primary_experts"])


def _layouts() -> tuple:
    """A (turns its heads, slides a window) pair a layer held."""
    n = CFG["num_hidden_layers"]
    return tuple(zip(CFG["rope_layout"][:n],
                     CFG["sliding_window_layout"][:n]))


# ------------------------------------------------------------ reference
def _init(key):
    d, f = CFG["hidden_size"], CFG["model"]["features"]
    qh, kvh, hd = CFG["num_attention_heads"], CFG["num_key_value_heads"], \
        CFG["head_dim"]
    e = CFG["moe_ffn_hidden_size"]
    _, held, routed = _held()
    layers = len(_layouts())
    keys = iter(jax.random.split(key, 8 * layers + 2))

    def normal(*shape):
        return STD * jax.random.normal(next(keys), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm(width):
        return {"scale": jnp.ones((width,), jnp.float32)}

    # the tree the program's flax module builds (models/hybrid.py): no
    # router bias, no shared expert
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(d)}
    for i in range(layers):
        out[f"layer{i}"] = {
            "norm1": norm(d), "norm2": norm(d),
            "mixer": {"q": kernel(d, qh * hd), "k": kernel(d, kvh * hd),
                      "v": kernel(d, kvh * hd), "o": kernel(qh * hd, d)},
            "moe": {"router": normal(d, routed),
                    "experts_in": normal(held, d, 2 * e),
                    "experts_out": normal(held, e, d)}}
    return out


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


def _attention(p, u, turns: int, slides: int):
    """Query head i reads key/value head i // (heads / groups); heads
    turned where `turns`, the last `sliding_window_size` keys where
    `slides`; a window's group and a block of queries at a time."""
    B, T, _ = u.shape
    qh, g, hd = CFG["num_attention_heads"], CFG["num_key_value_heads"], \
        CFG["head_dim"]
    q = (u @ p["q"]["kernel"]).reshape(B, T, qh, hd)
    k = (u @ p["k"]["kernel"]).reshape(B, T, g, hd)
    if turns:
        q, k = _rotary(q, CFG["rope_theta"]), _rotary(k, CFG["rope_theta"])
    v = (u @ p["v"]["kernel"]).reshape(B, T, g, hd)
    q = q.reshape(B, T, g, qh // g, hd)
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(qb, kg, vg, start):              # [blk, R, D], [T, D]
        s = jnp.einsum("qrd,kd->rqk", qb, kg) \
            * jnp.asarray(1.0 / math.sqrt(hd), qb.dtype)
        t = (start + jnp.arange(blk))[:, None]
        mask = j <= t
        if slides:
            mask = mask & (j > t - CFG["sliding_window_size"])
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        return jnp.einsum("rqk,kd->qrd",
                          jax.nn.softmax(s, axis=-1).astype(vg.dtype), vg)

    def group(args):
        qg, kg, vg = args                      # [T, R, D], [T, D]
        qb = qg.reshape((T // blk, blk) + qg.shape[1:])
        o = jax.lax.map(lambda a: block(a[0], kg, vg, a[1]),
                        (qb, jnp.arange(T // blk) * blk))
        return o.reshape(qg.shape)

    # one window's one group at a time: [B · groups, T, …]
    rows = lambda t: jnp.moveaxis(t, 2, 1).reshape(  # noqa: E731
        (B * g, T) + t.shape[3:])
    o = jax.lax.map(group, (rows(q), rows(k), rows(v)))
    o = jnp.moveaxis(o.reshape(B, g, T, qh // g, hd), 1, 2)
    return o.reshape(B, T, qh * hd) @ p["o"]["kernel"]


def _route(p, x):
    """x [N, d], the block's own input → (experts [N, k] of all routed
    over, weights [N, k], assignments [routed over]): the k largest raw
    logits and a softmax over those k.  Scores in float32; the driver
    runs the reference under `highest`."""
    k = CFG["moe_num_active_primary_experts"]
    s = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    picked, experts = jax.lax.top_k(s, k)
    e = jnp.exp(picked - jnp.max(picked, axis=-1, keepdims=True))
    weights = e / jnp.sum(e, axis=-1, keepdims=True)
    counts = jnp.sum(jax.nn.one_hot(experts, s.shape[-1], dtype=jnp.int32),
                     axis=(0, 1))
    return experts, weights, counts


def _relu_gated(x, w_in, w_out):
    gate, value = jnp.split(x @ w_in, 2, axis=-1)
    return (jnp.maximum(gate, 0) * value) @ w_out


def _experts_layer(p, u, routed_on):
    """Σ_k w_k E_ik(u) over the experts HELD, routed on `routed_on`
    (the block's input), each expert applied to every token and weighted
    by zero where it was not selected; no shared expert → (the layer's
    output, its assignments to every expert)."""
    B, T, d = u.shape
    first, held, _ = _held()
    x = u.reshape(B * T, d)
    experts, weights, counts = _route(p, routed_on.reshape(B * T, d))
    dense_w = jnp.sum(
        jnp.where(experts[..., None] == first + jnp.arange(held),
                  weights[..., None], 0.0), axis=1).astype(x.dtype)

    @jax.checkpoint
    def one(acc, ew):
        w_in, w_out, w = ew
        return acc + _relu_gated(x, w_in, w_out) * w[:, None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_in"], p["experts_out"], dense_w.T))
    return routed.reshape(B, T, d), counts


def _block(p, h, turns: int, slides: int):
    @jax.checkpoint
    def block(p, h):
        mixed = h + _attention(p["mixer"], _rms_norm(p["norm1"], h),
                               turns, slides)
        out, counts = _experts_layer(p["moe"], _rms_norm(p["norm2"], mixed),
                                     routed_on=h)
        return mixed + out, counts
    return block(p, h)


def _forward(params, x):
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    counts = []
    for i, (turns, slides) in enumerate(_layouts()):
        h, c = _block(params[f"layer{i}"], h, turns, slides)
        counts.append(c)
    h = _rms_norm(params["norm_f"], h)
    return h @ params["head"]["kernel"] + params["head"]["bias"], counts


# ------------------------------------------------ the experts' placement
def _balanced_share(counts, chips: int) -> np.ndarray:
    """The experts of the chip's share whose load lies nearest the mean
    when the experts are dealt to `chips` chips of equal room, the
    busiest first and each to the chip with the least load so far (the
    longest-processing-time rule of an expert-placement balancer)."""
    counts = np.asarray(counts, np.int64)
    room = len(counts) // chips
    load, share = np.zeros(chips, np.int64), [[] for _ in range(chips)]
    for e in np.argsort(-counts, kind="stable"):
        free = [c for c in range(chips) if len(share[c]) < room]
        c = min(free, key=lambda c: (load[c], c))
        load[c] += counts[e]
        share[c].append(int(e))
    nearest = min(range(chips),
                  key=lambda c: (abs(load[c] * chips - counts.sum()), c))
    return np.sort(share[nearest])


def _place(params: dict, x) -> dict:
    """The seeded weights with each router's outputs relabelled so that
    the experts held here are a balanced share of its load on the window
    `x`: layer by layer on the stream the layers before it, placed, hand
    on.  A relabelling alone: every column keeps its seeded values, and
    held and absent experts keep their order among themselves."""
    first, held, routed = _held()
    step = jax.jit(lambda p, h, turns, slides: _block(p, h, turns, slides),
                   static_argnums=(2, 3))
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    out = dict(params)
    for i, (turns, slides) in enumerate(_layouts()):
        p = params[f"layer{i}"]
        counts = _route(p["moe"], h.reshape(-1, h.shape[-1]))[2]
        here = _balanced_share(jax.device_get(counts), routed // held)
        absent = np.setdiff1d(np.arange(routed), here)
        order = np.concatenate([absent[:first], here, absent[first:]])
        p = dict(p, moe=dict(p["moe"], router=p["moe"]["router"][:, order]))
        h, out[f"layer{i}"] = step(p, h, turns, slides)[0], p
    return out


def init_params(seed: int) -> dict:
    """The seeded weights (the imported maker's one jitted call); where
    a chip holds a share of the experts and the adapter's trainer has
    read its stream's first batch, the experts placed on that batch."""
    params = _km.init_params(seed)
    _, held, routed = _held()
    if held == routed or _STREAM.get("first_batch") is None:
        return params
    return _place(params, jnp.asarray(_STREAM["first_batch"]))


# -------------------------------------------------------------- adapter
def hybrid_config(cfg: dict):
    """The program's `HybridConfig` of a configuration file."""
    from iotml.models.hybrid import HybridConfig

    fields = {f.name for f in dataclasses.fields(HybridConfig)}
    if not {"attn_window", "rope_layout", "router_input"} <= fields:
        raise SystemExit(
            "this checkout's program has no sliding-window attention layer, "
            "no rotary positions by layer and no router on the block's "
            "input (iotml/models/hybrid.py): it cannot run "
            "sensorformer-smallthinker-21b-a3b")
    use(cfg)
    first, held, routed = _held()
    layouts = _layouts()
    head = cfg["head_dim"]
    return HybridConfig(
        d_model=cfg["hidden_size"],
        layer_types=tuple("window_attention" if slides else "attention"
                          for _, slides in layouts),
        ffn_types=("moe_ffn",) * len(layouts),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head,
        attention_multiplier=head ** -0.5,
        attn_rope_theta=float(cfg["rope_theta"]),
        rope_layout=tuple(int(turns) for turns, _ in layouts),
        attn_window=cfg["sliding_window_size"], eps=cfg["rms_norm_eps"],
        experts=routed, experts_held=(first, held),
        top_k=cfg["moe_num_active_primary_experts"],
        expert_dim=cfg["moe_ffn_hidden_size"], shared_dim=0,
        expert_form="relu_gated", router_form="softmax_topk",
        router_input="block", routed_scale=1.0,
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0)


#: the stream's first batch as the newest trainer of this module read it
_STREAM = {}


class Trainer(_km.Trainer):
    """The sparse-expert file's adapter; ahead of its weights it reads
    its stream's first batch through a cursor of its own (another
    group, from the log's start, the trainer's own batching: what the
    first job will train on), for the experts' placement — a
    deployment's balancer places them by the load it has seen."""

    def __init__(self, run):
        from iotml.data.dataset import SensorBatches
        from iotml.stream.consumer import StreamConsumer

        super().__init__(run)
        job, topic = run.cfg["job"], run.cfg["deployment"]["topic"]
        batches = iter(SensorBatches(
            StreamConsumer.from_committed(
                run.broker, topic, range(run.broker.topic(topic).partitions),
                group=self.group + "-placement"),
            normalizer=normalizer(run.cfg), batch_size=job["batch_size"],
            take=job["take_batches"], window=job["window"],
            only_normal=False))
        _STREAM["first_batch"] = next(batches).x
        batches.close()


# the sparse-expert file's loss, fit, weights' maker and adapter, around
# this file's block and this source's key for the experts held
_km._init, _km._forward, _km.hybrid_config, _km._held = \
    _init, _forward, hybrid_config, _held
forward, loss_fn, make_fit = _km.forward, _km.loss_fn, _km.make_fit
normalizer = _km.normalizer
