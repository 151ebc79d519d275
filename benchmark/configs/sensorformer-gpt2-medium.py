"""sensorformer-gpt2-medium: the plain reference, and the adapter that
runs the fit leg of `run_streaming_app`'s train mode (cli/_app.py) as
`cli/lstm.py` instantiates it, with the program's `SensorFormer` at the
source's widths as `make_model`, job after job.

The reference: the block's equations as the source states them —
pre-norm, causal softmax attention over 16 heads of 64 with biases, a
gelu (tanh form) MLP of four times the width, learned positions, a final
LayerNorm — in `jax.numpy`; `jax.grad` of the masked MSE; Adam with
optax's defaults (the fit loop is the one `car-autoencoder.py` writes
out).  Each block is recomputed in the backward pass (`jax.checkpoint`)
so that the plain attention's [B, H, T, T] scores of 24 layers need not
be held beside the program's state.  Imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_car_autoencoder", os.path.join(_here, "car-autoencoder.py"))
_ae = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ae)
MODEL = {}   # the configuration's "model" group, set by use()
STD = 0.02   # the source's initializer_range
LN_EPS = 1e-6


def use(cfg: dict) -> None:
    """The sizes this run's configuration file states."""
    MODEL.clear()
    MODEL.update(cfg["model"])


def make_fit(loss, epochs: int):
    return _ae.make_fit(
        loss, epochs, lr=MODEL["optimizer"]["learning_rate"])


# ------------------------------------------------------------ reference
def _init(key, f, d, h, layers, ratio, max_len):
    keys = iter(jax.random.split(key, 4 * layers + 3))

    def normal(shape):
        return STD * jax.random.normal(next(keys), shape, jnp.float32)

    def dense(shape, bias):
        return {"kernel": normal(shape),
                "bias": jnp.zeros(bias, jnp.float32)}

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    # the tree the program's flax module builds (models/transformer.py)
    out = {"embed": dense((f, d), (d,)),
           "pos": {"embedding": normal((max_len, d))},
           "ln_f": norm(), "head": dense((d, f), (f,))}
    for i in range(layers):
        out[f"block{i}"] = {
            "ln1": norm(), "ln2": norm(),
            "attn": {"qkv": dense((d, 3, h, d // h), (3, h, d // h)),
                     "out": dense((h, d // h, d), (d,))},
            "mlp_in": dense((d, ratio * d), (ratio * d,)),
            "mlp_out": dense((ratio * d, d), (d,))}
    return out


def init_params(seed: int) -> dict:
    """One jitted call on the device, from the seed."""
    m = MODEL
    return jax.jit(_init, static_argnums=(1, 2, 3, 4, 5, 6))(
        jax.random.PRNGKey(seed), m["features"], m["d_model"],
        m["num_heads"], m["num_layers"], m["mlp_ratio"], m["max_len"])


def _layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + jnp.asarray(LN_EPS, x.dtype)) \
        * p["scale"] + p["bias"]


def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


@jax.checkpoint
def _block(p, x):
    a = _layer_norm(p["ln1"], x)
    qkv = jnp.einsum("btd,dchk->btchk", a, p["attn"]["qkv"]["kernel"]) \
        + p["attn"]["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    t = x.shape[1]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, jnp.asarray(-1e30, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, p["attn"]["out"]["kernel"]) \
        + p["attn"]["out"]["bias"]
    m = _layer_norm(p["ln2"], x)
    m = _gelu(m @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"])
    return x + m @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]


def forward(params, x):
    h = x @ params["embed"]["kernel"] + params["embed"]["bias"] \
        + params["pos"]["embedding"][:x.shape[1]]
    for i in range(len([k for k in params if k.startswith("block")])):
        h = _block(params[f"block{i}"], h)
    h = _layer_norm(params["ln_f"], h)
    return h @ params["head"]["kernel"] + params["head"]["bias"]


def loss_fn(params, x, y, mask, operands=None):
    """Every position's output against the record after the window: what
    the program's windowed supervised loss computes (y is [B, 1, F])."""
    assert operands is None
    out = forward(params, x)
    m = mask[:, None, None].astype(out.dtype)
    return jnp.sum(jnp.square(out - y) * m) / jnp.maximum(
        jnp.sum(m) * (out.shape[1] * out.shape[2]), 1.0)


# -------------------------------------------------------------- adapter
class Trainer:
    """The fit leg of `run_streaming_app`'s train mode (cli/_app.py), run
    job after job on one Trainer and one cursor.  No job stores a
    checkpoint (the configuration's file says why), so none commits."""

    commits = False

    def __init__(self, run):
        from iotml.data.dataset import SensorBatches
        from iotml.models.transformer import SensorFormer
        from iotml.stream.consumer import StreamConsumer
        from iotml.train.loop import Trainer as ProgramTrainer

        job, topic = run.cfg["job"], run.cfg["deployment"]["topic"]
        m = run.cfg["model"]
        self.group = "cardata-sensorformer"
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
            SensorFormer(features=m["features"], d_model=m["d_model"],
                         num_heads=m["num_heads"],
                         num_layers=m["num_layers"], max_len=m["max_len"],
                         attn_mode=mode),
            supervised=True,
            learning_rate=m["optimizer"]["learning_rate"])
        self.epochs = job["epochs"]
        self.jobs = 0
        self.min_available = job["batch_size"] * job["take_batches"] \
            + job["window"] + 1
        self._fit = self.trainer.fit_compiled

    def batcher(self):
        return self.batches

    def set_batcher(self, b):
        self.batches = b

    def seed_weights(self, params, sample_x):
        self.trainer._ensure_state(sample_x)
        self.trainer.state = self.trainer.state.replace(params=params)

    def state(self):
        s = self.trainer.state
        adam = s.opt_state[0]
        return jax.device_get((s.params, adam.mu, adam.nu))

    def wrap(self, span):
        self.one_job = span("bench.round", self.one_job)
        self._fit = span("bench.fit_compiled",
                         _ae.keep_losses(self, self._fit))

    def one_job(self) -> dict:
        history = self._fit(self.batches, epochs=self.epochs)
        if not history["loss"]:
            return {}
        self.jobs += 1
        return {"round": self.jobs, "loss": float(history["loss"][-1]),
                "losses": history["loss"],
                "records": history["records"][-1],
                "fit": history["fit"], "interpret": history["interpret"]}

    def run(self, stop, on_round):
        while not stop():
            stats = self.one_job()
            if stats:
                on_round(stats)
        return self.jobs

    def artifacts_ok(self, rounds: int) -> bool:
        return True
