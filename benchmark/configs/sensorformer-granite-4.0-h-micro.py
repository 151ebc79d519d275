"""sensorformer-granite-4.0-h-micro: the plain reference, and the adapter
that runs the fit leg of `run_streaming_app`'s train mode (cli/_app.py)
as `cli/lstm.py` instantiates it, with the program's `SensorHybrid` at
the source's widths as `make_model`, job after job.

The reference: Granite 4.0-H's layer equations as its `config.json`
states them (the configuration's file has them in words), in
`jax.numpy` — weight-only RMSNorm, the three multipliers, a gated-SiLU
MLP, causal softmax attention of 32 query heads over 8 key/value heads
with no positions, and the Mamba-2 mixer with its selective state-space
recurrence computed STEP BY STEP over t,

    S_t = exp(Δ_t a) S_{t-1} + Δ_t x_t ⊗ B_t        y_t = S_t C_t + D x_t

as a `lax.scan` — nothing of the chunked algorithm the program runs.
The convolution is `lax.conv_general_dilated` with one group a channel
(the program adds four shifted products).  `jax.grad` of the masked
MSE; Adam written out.  So that it fits one chip beside nothing: each
block is recomputed in the backward pass, the recurrence is scanned in
checkpointed segments (its backward holds a segment's states of 2 MiB,
not a window's 4,096), the attention of one key/value group at a time
(four query heads against their shared k and v: [4, T, T] scores, not
[32, T, T]), and the fit donates its parameters.  Imports nothing of
the program.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os
import weakref

import jax
import jax.numpy as jnp

# the accepted sequence configuration's adapter: this one's is that
# around another model (the file imports nothing of the program either)
_spec = importlib.util.spec_from_file_location(
    "bench_sensorformer_gpt2_medium", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-gpt2-medium.py"))
_sf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_sf)
CFG = {}   # this run's configuration file, set by use()
STD = 0.02
B1, B2, EPS = 0.9, 0.999, 1e-8   # Adam, optax's defaults
#: the adapter's trainers: their device state goes before the reference
#: runs (see make_fit)
_TRAINERS = weakref.WeakSet()


def use(cfg: dict) -> None:
    """The sizes this run's configuration file states."""
    CFG.clear()
    CFG.update(cfg)


def _kinds() -> tuple:
    return tuple(CFG["layer_types"][:CFG["num_hidden_layers"]])


def _inner() -> int:
    inner = CFG["mamba_n_heads"] * CFG["mamba_d_head"]
    if inner != CFG["mamba_expand"] * CFG["hidden_size"] \
            or CFG["mamba_n_groups"] != 1:
        raise ValueError("state heads x head size is not expand x hidden "
                         "size, or B and C have more than one group")
    return inner


# ------------------------------------------------------------ reference
def _init(key):
    d, f = CFG["hidden_size"], CFG["model"]["features"]
    inner, n, hs = _inner(), CFG["mamba_d_state"], CFG["mamba_n_heads"]
    kv = CFG["num_key_value_heads"] * (d // CFG["num_attention_heads"])
    mlp, k_conv = CFG["shared_intermediate_size"], CFG["mamba_d_conv"]
    kinds = _kinds()
    keys = iter(jax.random.split(key, 8 * len(kinds) + 2))

    def kernel(*shape):
        return {"kernel": STD * jax.random.normal(next(keys), shape,
                                                  jnp.float32)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm(width):
        return {"scale": jnp.ones((width,), jnp.float32)}

    def mamba():
        dt = jnp.exp(jax.random.uniform(next(keys), (hs,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {"in_proj": kernel(d, 2 * inner + 2 * n + hs),
                "conv_kernel": kernel(k_conv, inner + 2 * n)["kernel"],
                "conv_bias": jnp.zeros((inner + 2 * n,), jnp.float32),
                # softplus(dt_bias) = dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (hs,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((hs,), jnp.float32),
                "norm": norm(inner), "out_proj": kernel(inner, d)}

    def attention():
        return {"q": kernel(d, d), "k": kernel(d, kv), "v": kernel(d, kv),
                "o": kernel(d, d)}

    # the tree the program's flax module builds (models/hybrid.py)
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(d)}
    for i, kind in enumerate(kinds):
        out[f"layer{i}"] = {
            "norm1": norm(d), "norm2": norm(d),
            "mixer": mamba() if kind == "mamba" else attention(),
            "mlp_in": kernel(d, 2 * mlp), "mlp_out": kernel(mlp, d)}
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


def _recurrence(x, dt, a, b, c):
    """S_t = exp(Δ_t a) S_{t-1} + Δ_t x_t ⊗ B_t, y_t = S_t C_t, one
    position at a time from S_0 = 0.  x [B, T, H, P], dt [B, T, H],
    a [H], b and c [B, T, N] → y [B, T, H, P]."""
    B, T, H, P = x.shape
    seg = max(s for s in range(1, 65) if T % s == 0)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    @jax.checkpoint
    def segment(state, inps):
        return jax.lax.scan(step, state, inps)

    def by_segment(v):   # [B, T, ...] -> [T / seg, seg, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((T // seg, seg) + v.shape[1:])

    state0 = jnp.zeros((B, H, P, b.shape[-1]), x.dtype)
    _, ys = jax.lax.scan(segment, state0,
                         tuple(by_segment(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(ys.reshape((T,) + ys.shape[2:]), 0, 1)


def _mamba(p, u):
    B, T, _ = u.shape
    inner, n = _inner(), CFG["mamba_d_state"]
    h, k_conv = CFG["mamba_n_heads"], CFG["mamba_d_conv"]
    z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * n], axis=-1)
    xbc = jax.lax.conv_general_dilated(
        xbc, p["conv_kernel"][:, None, :], window_strides=(1,),
        padding=[(k_conv - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=inner + 2 * n) + p["conv_bias"]
    x, b, c = jnp.split(jax.nn.silu(xbc), [inner, inner + n], axis=-1)
    x = x.reshape(B, T, h, inner // h)
    y = _recurrence(x, jax.nn.softplus(dt + p["dt_bias"]),
                    -jnp.exp(p["A_log"]), b, c) + p["D"][:, None] * x
    y = _rms_norm(p["norm"], y.reshape(B, T, inner) * jax.nn.silu(z))
    return y @ p["out_proj"]["kernel"]


def _attention(p, u):
    B, T, d = u.shape
    h, g = CFG["num_attention_heads"], CFG["num_key_value_heads"]
    hd = d // h
    # query head i reads key/value head i // (h / g): k and v repeated
    q = (u @ p["q"]["kernel"]).reshape(B, T, g, h // g, hd)
    k = (u @ p["k"]["kernel"]).reshape(B, T, g, hd)
    v = (u @ p["v"]["kernel"]).reshape(B, T, g, hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv
        s = jnp.einsum("bqrd,bkd->brqk", qg, kg) \
            * jnp.asarray(CFG["attention_multiplier"], qg.dtype)
        s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
        return jnp.einsum("brqk,bkd->bqrd", jax.nn.softmax(s, axis=-1), vg)

    o = jax.lax.map(group, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2).reshape(B, T, d) @ p["o"]["kernel"]


@functools.partial(jax.checkpoint, static_argnums=(0,))
def _block(kind, p, h):
    r = jnp.asarray(CFG["residual_multiplier"], h.dtype)
    mixer = _mamba if kind == "mamba" else _attention
    h = h + r * mixer(p["mixer"], _rms_norm(p["norm1"], h))
    gate, value = jnp.split(
        _rms_norm(p["norm2"], h) @ p["mlp_in"]["kernel"], 2, axis=-1)
    return h + r * ((jax.nn.silu(gate) * value) @ p["mlp_out"]["kernel"])


def forward(params, x):
    h = jnp.asarray(CFG["embedding_multiplier"], x.dtype) \
        * (x @ params["embed"]["kernel"] + params["embed"]["bias"])
    for i, kind in enumerate(_kinds()):
        h = _block(kind, params[f"layer{i}"], h)
    h = _rms_norm(params["norm_f"], h)
    return (h @ params["head"]["kernel"] + params["head"]["bias"]) \
        / jnp.asarray(CFG["logits_scaling"], h.dtype)


def loss_fn(params, x, y, mask, operands=None):
    """Every position's output against the record after the window: what
    the program's windowed supervised loss computes (y is [B, 1, F])."""
    assert operands is None
    out = forward(params, x)
    m = mask[:, None, None].astype(out.dtype)
    return jnp.sum(jnp.square(out - y) * m) / jnp.maximum(
        jnp.sum(m) * (out.shape[1] * out.shape[2]), 1.0)


def make_fit(loss, epochs: int):
    """One job as the configuration states it: `epochs` passes over the
    same batches, Adam after every batch.  Returns (params, mu, nu,
    per-epoch mean loss).  The program donates its copy of the
    parameters handed in, which stay the caller's.

    A chip holds the reference's 12 GB (weights, gradients, both
    moments) or the trainer's, not both: whatever trainer this adapter
    built gives its device state up first."""
    for t in list(_TRAINERS):
        t.release()
    lr = CFG["model"]["optimizer"]["learning_rate"]

    def fit(params, xs, ys, masks):
        dt = jax.tree.leaves(params)[0].dtype
        zeros = jax.tree.map(jnp.zeros_like, params)

        def step(carry, inp):
            p, mu, nu, t = carry
            x, y, m = inp
            val, g = jax.value_and_grad(loss)(p, x, y, m)
            t = t + 1
            mu = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, nu, g)
            c1 = (1 - B1 ** t).astype(dt)
            c2 = (1 - B2 ** t).astype(dt)
            p = jax.tree.map(
                lambda w, a, b: w - (lr * (a / c1)
                                     / (jnp.sqrt(b / c2) + EPS)).astype(dt),
                p, mu, nu)
            return (p, mu, nu, t), val

        def epoch(carry, _):
            carry, vals = jax.lax.scan(step, carry, (xs, ys, masks))
            return carry, jnp.mean(vals.astype(jnp.float32))

        (p, mu, nu, _), losses = jax.lax.scan(
            epoch, (params, zeros, zeros, jnp.zeros((), jnp.float32)),
            None, length=epochs)
        return p, mu, nu, losses

    donating = jax.jit(fit, donate_argnums=(0,))
    return lambda params, *batches: donating(
        jax.tree.map(jnp.array, params), *batches)


# -------------------------------------------------------------- adapter
def hybrid_config(cfg: dict):
    """The program's `HybridConfig` of a configuration file."""
    from iotml.models.hybrid import HybridConfig

    return HybridConfig(
        d_model=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["shared_intermediate_size"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], conv_width=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"], eps=cfg["rms_norm_eps"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=float(cfg["logits_scaling"]))


class Trainer(_sf.Trainer):
    """The accepted sequence configuration's adapter — the fit leg of
    `run_streaming_app`'s train mode, job after job on one Trainer and
    one cursor, no job storing a checkpoint or committing — around the
    program's `SensorHybrid`, and able to give the chip back."""

    def __init__(self, run):
        from iotml.data.dataset import SensorBatches
        from iotml.models.hybrid import SensorHybrid
        from iotml.stream.consumer import StreamConsumer
        from iotml.train.loop import Trainer as ProgramTrainer

        job, topic = run.cfg["job"], run.cfg["deployment"]["topic"]
        m = run.cfg["model"]
        _inner()   # the file's widths agree with one another
        self.group = "cardata-sensorhybrid"
        parts = range(run.broker.topic(topic).partitions)
        self.consumer = StreamConsumer.from_committed(
            run.broker, topic, parts, group=self.group)
        self.batches = SensorBatches(
            self.consumer, batch_size=job["batch_size"],
            take=job["take_batches"], window=job["window"],
            only_normal=False)
        # the Pallas kernel is the chip's path; a rehearsal on the CPU
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
        self._fit = self.trainer.fit_compiled
        _TRAINERS.add(self)

    def release(self):
        """Give the chip back: the state's arrays and the fit's loaded
        program.  The driver has read the state to the host by now, and
        no job follows."""
        state, self.trainer.state = self.trainer.state, None
        for a in jax.tree.leaves(state):
            if isinstance(a, jax.Array):
                a.delete()
        jax.clear_caches()
        stats = jax.devices()[0].memory_stats() or {}
        print("trainer released; on the chip now:",
              {k: stats[k] for k in ("bytes_in_use", "bytes_reserved")
               if k in stats}, flush=True)
