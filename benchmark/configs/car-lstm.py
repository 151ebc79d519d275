"""car-lstm: the plain reference, and the adapter that runs what
`cli/lstm.py` -> `run_streaming_app` runs in train mode, job after job.

The reference: four LSTM layers with relu cells and a dense head, as the
source's model states them, in `jax.numpy`; `jax.grad` of the masked MSE
against the next record; Adam with optax's defaults (the fit loop is the
one `car-autoencoder.py` writes out).  Imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
import math
import os
import shutil

import jax
import jax.numpy as jnp

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_car_autoencoder", os.path.join(_here, "car-autoencoder.py"))
_ae = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ae)
make_fit = _ae.make_fit

CELLS = ((18, 32), (32, 16), (16, 16), (16, 32))
HEAD = (32, 18)
LOOK_BACK = 1
GATES = "ifgo"


# ------------------------------------------------------------ reference
@jax.jit
def _init(key):
    def glorot(k, fi, fo):
        lim = math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(k, (fi, fo), jnp.float32, -lim, lim)

    keys = iter(jax.random.split(key, 8 * len(CELLS) + 1))
    out = {}
    for n, (fi, u) in enumerate(CELLS):
        cell = {}
        for g in GATES:
            cell["i" + g] = {"kernel": glorot(next(keys), fi, u)}
            cell["h" + g] = {"kernel": glorot(next(keys), u, u),
                             "bias": jnp.zeros((u,), jnp.float32)}
        # the tree the program's flax module builds (models/lstm.py)
        out[f"OptimizedLSTMCell_{n}"] = cell
    out["head"] = {"kernel": glorot(next(keys), *HEAD),
                   "bias": jnp.zeros((HEAD[1],), jnp.float32)}
    return out


def init_params(seed: int) -> dict:
    return _init(jax.random.PRNGKey(seed))


def _lstm(cell, xs, operands):
    """xs [B, T, F] -> hidden sequence [B, T, U]; zero initial carry."""
    dot = lambda a, w: _ae._dot(a, w, operands)  # noqa: E731
    u = cell["hi"]["bias"].shape[0]
    zeros = jnp.zeros((xs.shape[0], u), xs.dtype)

    def step(carry, x):
        c, h = carry
        pre = {g: dot(x, cell["i" + g]["kernel"])
               + dot(h, cell["h" + g]["kernel"]) + cell["h" + g]["bias"]
               for g in GATES}
        i, f, o = (jax.nn.sigmoid(pre[g]) for g in "ifo")
        c = f * c + i * jax.nn.relu(pre["g"])
        h = o * jax.nn.relu(c)
        return (c, h), h

    _, hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def forward(params, x, operands=None):
    h = _lstm(params["OptimizedLSTMCell_0"], x, operands)
    h = _lstm(params["OptimizedLSTMCell_1"], h, operands)[:, -1, :]
    h = jnp.repeat(h[:, None, :], LOOK_BACK, axis=1)
    h = _lstm(params["OptimizedLSTMCell_2"], h, operands)
    h = _lstm(params["OptimizedLSTMCell_3"], h, operands)
    return _ae._dot(h, params["head"]["kernel"], operands) \
        + params["head"]["bias"]


def loss_fn(params, x, y, mask, operands=None):
    out = forward(params, x, operands)
    m = mask[:, None, None].astype(out.dtype)
    return jnp.sum(jnp.square(out - y) * m) / jnp.maximum(
        jnp.sum(m) * (y.shape[1] * y.shape[2]), 1.0)


# -------------------------------------------------------------- adapter
class Trainer:
    """The train mode of `run_streaming_app` (cli/_app.py), its body run
    job after job on one Trainer and one cursor."""

    def __init__(self, run):
        from iotml.data.dataset import SensorBatches
        from iotml.models.lstm import LSTMSeq2Seq
        from iotml.stream.consumer import StreamConsumer
        from iotml.train.artifacts import ArtifactStore
        from iotml.train.loop import Trainer as ProgramTrainer

        job, topic = run.cfg["job"], run.cfg["deployment"]["topic"]
        self.run_ctx = run
        self.group = "cardata-lstm"
        parts = range(run.broker.topic(topic).partitions)
        self.consumer = StreamConsumer.from_committed(
            run.broker, topic, parts, group=self.group)
        self.batches = SensorBatches(
            self.consumer, batch_size=job["batch_size"],
            take=job["take_batches"], window=job["window"],
            only_normal=False)
        self.trainer = ProgramTrainer(
            LSTMSeq2Seq(features=18, look_back=job["window"]),
            supervised=True)
        self.epochs = job["epochs"]
        self.store = ArtifactStore(run.path("artifacts"))
        self.jobs = 0
        self.min_available = job["batch_size"] * job["take_batches"] \
            + job["window"] + 1
        self._fit = self.trainer.fit_compiled
        self._save = self._save_job
        self._commit = self.consumer.commit

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
        self._save = span("bench.publish", self._save)
        self._commit = span("bench.commit", self._commit)

    def _save_job(self, name: str) -> None:
        from iotml.train.checkpoint import CheckpointManager

        ckpt_dir = self.run_ctx.path("ckpt")
        path = CheckpointManager(ckpt_dir).save(
            self.trainer.state, cursors=self.consumer.positions())
        self.store.upload_tree(path, name)
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    def one_job(self) -> dict:
        history = self._fit(self.batches, epochs=self.epochs)
        if not history["loss"]:
            return {}
        self.jobs += 1
        self._save(f"lstm-model.j{self.jobs}")
        # commit AFTER the checkpoint is stored (cli/_app.py)
        self._commit()
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
        return all(self.store.exists(f"lstm-model.j{k}.zip")
                   for k in range(1, rounds + 1))
