"""sensorformer-kimi-vl-a3b-instruct: the plain reference, and the
adapter that runs the fit leg of `run_streaming_app`'s train mode
(cli/_app.py) as `cli/lstm.py` instantiates it, with the program's
`SensorHybrid` at the source's widths as `make_model`, job after job.

The reference: the decoder's layer equations as the source's
`config.json` states them (the configuration's file has them in words),
in `jax.numpy` — weight-only RMSNorm, latent attention with its one
shared rotary key head (rotary written out, plain softmax attention a
head at a time in blocks of queries so that T = 8,192 fits), the
sigmoid router with its selection-only bias, and the expert layer with
EVERY EXPERT HELD APPLIED DENSELY TO EVERY TOKEN and weighted by a
routing weight that is zero where it was not selected — no sort, no
tiles, no grouped product: nothing of the dispatch the program runs.
`jax.grad` of the masked MSE; Adam written out.  Each block is
recomputed in the backward pass, the experts one at a time, and the fit
donates its parameters.  Imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np

# the hybrid configuration's adapter (and through it the accepted
# sequence configuration's): this one's is that around another model
_spec = importlib.util.spec_from_file_location(
    "bench_sensorformer_granite_h_micro", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-granite-4.0-h-micro.py"))
_gh = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_gh)
if importlib.util.find_spec("iotml.models.latent_moe") is None:
    raise SystemExit("this checkout's program has no latent attention and "
                     "no expert layer (iotml/models/latent_moe.py): it "
                     "cannot run sensorformer-kimi-vl-a3b-instruct")
CFG = {}   # this run's configuration file, set by use()
STD = 0.02
B1, B2, EPS = 0.9, 0.999, 1e-8   # Adam, optax's defaults
Q_BLOCK = 1024                   # queries a block of the plain attention
#: the adapter's trainers: their device state goes before the reference
#: runs, and the first job's reports are compared with the reference's
_TRAINERS = weakref.WeakSet()


def use(cfg: dict) -> None:
    """The sizes this run's configuration file states."""
    CFG.clear()
    CFG.update(cfg)


def _ffn_kinds() -> tuple:
    """`dense_ffn` or `moe_ffn` a layer, by the source's rule: layer i
    routes if i >= first_k_dense_replace and i % moe_layer_freq == 0."""
    return tuple(
        "moe_ffn" if i >= CFG["first_k_dense_replace"]
        and i % CFG["moe_layer_freq"] == 0 else "dense_ffn"
        for i in range(CFG["num_hidden_layers"]))


def _held() -> tuple:
    """(first, count, routed over): the experts held here of all."""
    return (CFG["experts_held"]["first"], CFG["n_routed_experts"],
            CFG["published"]["n_routed_experts"])


# ------------------------------------------------------------ reference
def _init(key):
    d, f = CFG["hidden_size"], CFG["model"]["features"]
    h = CFG["num_attention_heads"]
    nope, rope = CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"]
    rank, dv = CFG["kv_lora_rank"], CFG["v_head_dim"]
    e, shared = CFG["moe_intermediate_size"], \
        CFG["n_shared_experts"] * CFG["moe_intermediate_size"]
    _, held, routed = _held()
    kinds = _ffn_kinds()
    keys = iter(jax.random.split(key, 12 * len(kinds) + 2))

    def normal(*shape):
        return STD * jax.random.normal(next(keys), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": normal(*shape)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm(width):
        return {"scale": jnp.ones((width,), jnp.float32)}

    # the tree the program's flax module builds (models/hybrid.py)
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(d)}
    for i, kind in enumerate(kinds):
        layer = {
            "norm1": norm(d), "norm2": norm(d),
            "mixer": {"q": kernel(d, h * (nope + rope)),
                      "kv_a": kernel(d, rank + rope), "kv_norm": norm(rank),
                      "kv_b": kernel(rank, h * (nope + dv)),
                      "o": kernel(h * dv, d)}}
        if kind == "moe_ffn":
            layer["moe"] = {
                "router": normal(d, routed), "router_bias": normal(routed),
                "experts_in": normal(held, d, 2 * e),
                "experts_out": normal(held, e, d),
                "shared_in": kernel(d, 2 * shared),
                "shared_out": kernel(shared, d)}
        else:
            layer["mlp_in"] = kernel(d, 2 * CFG["intermediate_size"])
            layer["mlp_out"] = kernel(CFG["intermediate_size"], d)
        out[f"layer{i}"] = layer
    return out


def init_params(seed: int) -> dict:
    """One jitted call on the device, from the seed (a fresh closure a
    call: `_init` reads the sizes `use` set, which a cached trace of it
    would not see change)."""
    return jax.jit(lambda key: _init(key))(jax.random.PRNGKey(seed))


def _rms_norm(p, x):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + jnp.asarray(CFG["rms_norm_eps"], x.dtype)) \
        * p["scale"]


def _rotary(x):
    """x [B, T, H, R]: features (2i, 2i+1) turned by t · θ^(−2i/R)."""
    T, R = x.shape[1], x.shape[-1]
    inv = 1.0 / (CFG["rope_theta"] ** (np.arange(0, R, 2) / R))
    angle = jnp.asarray(np.arange(T)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2].astype(jnp.float32), \
        x[..., 1::2].astype(jnp.float32)
    out = jnp.zeros(x.shape, jnp.float32)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    out = out.at[..., 1::2].set(even * sin + odd * cos)
    return out.astype(x.dtype)


def _attention(p, u):
    B, T, _ = u.shape
    h = CFG["num_attention_heads"]
    nope, rope = CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"]
    rank, dv = CFG["kv_lora_rank"], CFG["v_head_dim"]
    q = (u @ p["q"]["kernel"]).reshape(B, T, h, nope + rope)
    ckv = u @ p["kv_a"]["kernel"]
    c, k_pe = ckv[..., :rank], ckv[..., rank:]
    kv = (_rms_norm(p["kv_norm"], c) @ p["kv_b"]["kernel"]).reshape(
        B, T, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:])], axis=-1)
    k_pe = _rotary(k_pe[:, :, None, :])        # one head, shared by all
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))], axis=-1)
    v = kv[..., nope:]
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    pos_k = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qb, kh, vh, start = args               # [B, blk, D], [B, T, D], …
        s = jnp.einsum("bqd,bkd->bqk", qb, kh) \
            * jnp.asarray(1.0 / math.sqrt(nope + rope), qb.dtype)
        causal = (start + jnp.arange(blk))[:, None] >= pos_k[None, :]
        s = jnp.where(causal, s.astype(jnp.float32), -1e30)
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(s, axis=-1).astype(vh.dtype), vh)

    def head(args):
        qh, kh, vh = args                      # [B, T, D]
        qb = jnp.moveaxis(qh.reshape(B, T // blk, blk, -1), 1, 0)
        o = jax.lax.map(lambda a: block((a[0], kh, vh, a[1])),
                        (qb, jnp.arange(T // blk) * blk))
        return jnp.moveaxis(o, 0, 1).reshape(B, T, dv)

    o = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2).reshape(B, T, h * dv) @ p["o"]["kernel"]


def _gated(x, w_in, w_out):
    gate, value = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * value) @ w_out


def _route(p, x):
    """x [N, d] → (experts [N, k] of all routed over, weights [N, k],
    assignments [routed over]).  Scores in float32; the driver runs the
    reference under `highest`."""
    k = CFG["num_experts_per_tok"]
    s = jax.nn.sigmoid(x.astype(jnp.float32)
                       @ p["router"].astype(jnp.float32))
    _, experts = jax.lax.top_k(s + p["router_bias"], k)   # selection only
    picked = jnp.take_along_axis(s, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * CFG["routed_scaling_factor"]
    counts = jnp.sum(jax.nn.one_hot(experts, s.shape[-1], dtype=jnp.int32),
                     axis=(0, 1))
    return experts, weights, counts


def _experts_layer(p, u):
    """Σ_k w_k E_ik(u) over the experts HELD, each applied to every
    token and weighted by zero where it was not selected, + S(u)."""
    B, T, d = u.shape
    first, held, _ = _held()
    x = u.reshape(B * T, d)
    experts, weights, counts = _route(p, x)
    # [N, held]: the weight token n gives expert first + j, else 0
    dense_w = jnp.sum(
        jnp.where(experts[..., None] == first + jnp.arange(held),
                  weights[..., None], 0.0), axis=1).astype(x.dtype)

    @jax.checkpoint
    def one(acc, ew):
        w_in, w_out, w = ew
        return acc + _gated(x, w_in, w_out) * w[:, None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_in"], p["experts_out"], dense_w.T))
    shared = _gated(x, p["shared_in"]["kernel"], p["shared_out"]["kernel"])
    return (routed + shared).reshape(B, T, d), counts


def _ffn(p, u):
    """→ (the layer's output, its assignments to every expert or None)."""
    if "moe" in p:
        return _experts_layer(p["moe"], u)
    return _gated(u, p["mlp_in"]["kernel"], p["mlp_out"]["kernel"]), None


@jax.checkpoint
def _block(p, h):
    h = h + _attention(p["mixer"], _rms_norm(p["norm1"], h))
    out, counts = _ffn(p, _rms_norm(p["norm2"], h))
    return h + out, counts


def _forward(params, x):
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    counts = []
    for i in range(CFG["num_hidden_layers"]):
        h, c = _block(params[f"layer{i}"], h)
        counts += [] if c is None else [c]
    h = _rms_norm(params["norm_f"], h)
    return h @ params["head"]["kernel"] + params["head"]["bias"], counts


def forward(params, x):
    return _forward(params, x)[0]


def _loss_counts(params, x, y, mask):
    out, counts = _forward(params, x)
    m = mask[:, None, None].astype(out.dtype)
    loss = jnp.sum(jnp.square(out - y) * m) / jnp.maximum(
        jnp.sum(m) * (out.shape[1] * out.shape[2]), 1.0)
    return loss, jnp.stack(counts) if counts else jnp.zeros((0, 1), jnp.int32)


def loss_fn(params, x, y, mask, operands=None):
    """Every position's output against the record after the window: what
    the program's windowed supervised loss computes (y is [B, 1, F])."""
    assert operands is None
    return _loss_counts(params, x, y, mask)[0]


def make_fit(loss, epochs: int):
    """One job as the configuration states it: `epochs` passes over the
    same batches, Adam after every batch.  Returns (params, mu, nu,
    per-epoch mean loss), and says beside them how the job's assignments
    compare with the program's.  The program donates its copy of the
    parameters handed in, which stay the caller's.

    A chip holds the reference's state (weights, gradients, both
    moments) or the trainer's, not both: whatever trainer this adapter
    built gives its device state up first."""
    if loss is not loss_fn:
        raise ValueError("this configuration's fit follows its own loss")
    # what the reference's assignments are held to: the program's first
    # job where a trainer ran, and then it is a check of the run; else
    # the reference's own next pass (the control's lower precision)
    program, check, passes = None, None, []
    for t in list(_TRAINERS):
        program, check = t.first_counts, t.check
        print("assignments to the experts held a token and layer, and fit "
              "seconds, job by job:", t.by_job, flush=True)
        t.release()
    lr = CFG["model"]["optimizer"]["learning_rate"]

    def fit(params, xs, ys, masks):
        dt = jax.tree.leaves(params)[0].dtype
        zeros = jax.tree.map(jnp.zeros_like, params)

        def step(carry, inp):
            p, mu, nu, t = carry
            x, y, m = inp
            (val, counts), g = jax.value_and_grad(
                _loss_counts, has_aux=True)(p, x, y, m)
            t = t + 1
            mu = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, nu, g)
            c1 = (1 - B1 ** t).astype(dt)
            c2 = (1 - B2 ** t).astype(dt)
            p = jax.tree.map(
                lambda w, a, b: w - (lr * (a / c1)
                                     / (jnp.sqrt(b / c2) + EPS)).astype(dt),
                p, mu, nu)
            return (p, mu, nu, t), (val, counts)

        def epoch(carry, _):
            carry, (vals, counts) = jax.lax.scan(step, carry, (xs, ys, masks))
            return carry, (jnp.mean(vals.astype(jnp.float32)), counts)

        (p, mu, nu, _), (losses, counts) = jax.lax.scan(
            epoch, (params, zeros, zeros, jnp.zeros((), jnp.float32)),
            None, length=epochs)
        return p, mu, nu, losses, counts

    donating = jax.jit(fit, donate_argnums=(0,))

    def run(params, *batches):
        *state, counts = donating(jax.tree.map(jnp.array, params), *batches)
        passes.append(np.asarray(counts))
        _hold_assignments(passes[0], passes[-1] if program is None
                          and len(passes) > 1 else program, check)
        return tuple(state)

    return run


def _hold_assignments(reference, other, check) -> None:
    """The job's assignments to the experts held, by the reference and
    by the other side (the program, or the control's lower precision),
    and the share of them on which the two disagree: top-k is
    discontinuous, and the program's stream reaches the router rounded
    otherwise than under `highest`, so some assignments flip — few, or
    the router, the sort or the histogram is another one.  A check of
    the run where `check` is given.  [epochs, steps, layers, experts]."""
    first, held, _ = _held()
    if not reference.size:
        return
    ref_held = reference[..., first:first + held]
    line = {"reference_held": int(ref_held.sum()),
            "of": int(reference.sum())}
    if other is not None:
        other_held = other[..., first:first + held]
        # half the L1 distance of the histograms, a step and layer: the
        # assignments one side makes that the other does not, at least
        flipped = float(np.abs(other_held - ref_held).sum() / 2
                        / max(ref_held.sum(), 1))
        line.update(other_held=int(other_held.sum()), flipped_share=flipped)
    print("assignments to the experts held, first job:", line, flush=True)
    if other is not None and check is not None:
        check("assignment_flip_share", flipped,
              CFG["limits"]["train"]["assignment_flip_share"])


# -------------------------------------------------------------- adapter
def hybrid_config(cfg: dict):
    """The program's `HybridConfig` of a configuration file."""
    from iotml.models.hybrid import HybridConfig

    use(cfg)
    first, held, routed = _held()
    return HybridConfig(
        d_model=cfg["hidden_size"],
        layer_types=("mla",) * cfg["num_hidden_layers"],
        ffn_types=_ffn_kinds(),
        num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        experts=routed, experts_held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0)


def normalizer(cfg: dict):
    """The program's `Normalizer` over the ranges the configuration's
    file states (`normalization.ranges`: calibrated to the fleet, not
    the reference's hand-picked ones; a null range zeroes its field)."""
    from iotml.core.normalize import Normalizer
    from iotml.core.schema import CAR_SCHEMA

    fields = tuple(
        dataclasses.replace(f, norm=None if r is None else tuple(r))
        for f, r in zip(CAR_SCHEMA.sensor_fields,
                        cfg["normalization"]["ranges"]))
    return Normalizer(dataclasses.replace(CAR_SCHEMA, fields=fields))


class Trainer(_gh.Trainer):
    """The hybrid configuration's adapter — the fit leg of
    `run_streaming_app`'s train mode, job after job on one Trainer and
    one cursor, no job storing a checkpoint or committing, able to give
    the chip back — around the program's `SensorHybrid` as this
    configuration's file states it."""

    def __init__(self, run):
        from iotml.data.dataset import SensorBatches
        from iotml.models.hybrid import SensorHybrid
        from iotml.stream.consumer import StreamConsumer
        from iotml.train.loop import Trainer as ProgramTrainer

        job, topic = run.cfg["job"], run.cfg["deployment"]["topic"]
        m = run.cfg["model"]
        self.group = "cardata-sensorhybrid"
        parts = range(run.broker.topic(topic).partitions)
        self.consumer = StreamConsumer.from_committed(
            run.broker, topic, parts, group=self.group)
        self.batches = SensorBatches(
            self.consumer, normalizer=normalizer(run.cfg),
            batch_size=job["batch_size"], take=job["take_batches"],
            window=job["window"], only_normal=False)
        # the Pallas kernels are the chip's path; a rehearsal on the CPU
        # takes the program's jnp attention instead
        mode = m["attn_mode"] if run.on_chip() else "dense"
        self.trainer = ProgramTrainer(
            SensorHybrid(hybrid_config(run.cfg), features=m["features"],
                         attn_mode=mode),
            supervised=True,
            learning_rate=m["optimizer"]["learning_rate"])
        self.epochs = job["epochs"]
        self.jobs = 0
        self.min_available = job["batch_size"] * job["take_batches"] \
            + job["window"] + 1
        self._fit = self._watch_reports(self.trainer.fit_compiled)
        #: the first job's assignments, [epochs, steps, layers, experts],
        #: and the run's `check`, which the reference holds them to
        self.first_counts = None
        self.check = run.check
        #: (assignments held a token and layer, fit seconds) of every job
        self.by_job = []
        _TRAINERS.add(self)

    def _watch_reports(self, fit):
        first, held, _ = _held()

        def fitted(*args, **kw):
            history = fit(*args, **kw)
            if "reports" in history:
                layers = history["reports"]["reports"]
                counts = np.stack(
                    [np.asarray(jax.tree.leaves(layers[k])[0])
                     for k in sorted(layers, key=lambda k: int(k[5:]))],
                    axis=2)
                if self.first_counts is None:
                    self.first_counts = counts
                here = counts[..., first:first + held].sum()
                self.by_job.append((
                    round(float(here * CFG["num_experts_per_tok"]
                                / counts.sum()), 4),
                    round(sum(history["seconds"]), 4)))
            return history
        return fitted
