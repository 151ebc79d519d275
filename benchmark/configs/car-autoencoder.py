"""car-autoencoder: the plain reference, and the adapter that builds the
program's own trainer and scorer the way `cli.live` does.

The reference is the architecture's equations written out — forward,
loss, `jax.grad` of it, Adam with optax's defaults — in `jax.numpy`.
It imports nothing of the program and takes nothing the program made:
the benchmark makes the weights from the seed and hands the same ones to
both sides.  Run it under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LAYERS = ("encoder0", "encoder1", "decoder0", "decoder1")
DIMS = (18, 14, 7, 7, 18)
ACTS = (jnp.tanh, jax.nn.relu, jnp.tanh, jax.nn.relu)
L1 = 1e-7
LR, B1, B2, EPS = 1e-3, 0.9, 0.999, 1e-8


# ------------------------------------------------------------ reference
@jax.jit
def _init(key):
    out = {}
    for k, name, fi, fo in zip(jax.random.split(key, 4), LAYERS,
                               DIMS[:-1], DIMS[1:]):
        lim = math.sqrt(6.0 / (fi + fo))
        out[name] = {"kernel": jax.random.uniform(k, (fi, fo), jnp.float32,
                                                  -lim, lim),
                     "bias": jnp.zeros((fo,), jnp.float32)}
    return out


def init_params(seed: int) -> dict:
    """Glorot-uniform kernels, zero biases, one jitted call on the device."""
    return _init(jax.random.PRNGKey(seed))


def _dot(a, w, operands):
    if operands is None:
        return jnp.dot(a, w)
    # the TPU's default float32 product: operands rounded, float32 sums
    return jnp.dot(a.astype(operands), w.astype(operands),
                   preferred_element_type=jnp.float32)


def forward(params, x, operands=None, with_h1=False):
    h, h1 = x, None
    for i, (name, act) in enumerate(zip(LAYERS, ACTS)):
        h = act(_dot(h, params[name]["kernel"], operands)
                + params[name]["bias"])
        if i == 0:
            h1 = h
    return (h, h1) if with_h1 else h


def loss_fn(params, x, y, mask, operands=None):
    del y  # an autoencoder targets its input
    out, h1 = forward(params, x, operands, with_h1=True)
    m = mask[:, None].astype(out.dtype)
    mse = jnp.sum(jnp.square(out - x) * m) / jnp.maximum(
        jnp.sum(m) * x.shape[1], 1.0)
    return mse + L1 * jnp.sum(jnp.abs(h1)) / x.shape[0]


def make_fit(loss, epochs: int, operands=None, lr: float = LR):
    """One job as the configuration states it: `epochs` passes over the
    same batches, Adam (optax defaults, rate `lr`) after every batch.
    Returns (params, mu, nu, per-epoch mean loss)."""

    def fit(params, xs, ys, masks):
        dt = jax.tree.leaves(params)[0].dtype
        zeros = jax.tree.map(jnp.zeros_like, params)

        def step(carry, inp):
            p, mu, nu, t = carry
            x, y, m = inp
            val, g = jax.value_and_grad(loss)(p, x, y, m, operands)
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

    return jax.jit(fit)


# -------------------------------------------------------------- adapter
def keep_losses(holder, fit):
    """`fit`, leaving its newest per-epoch losses on `holder`."""
    def kept(*args, **kw):
        history = fit(*args, **kw)
        holder.last_losses = history["loss"]
        return history
    return kept


def _normalizer(cfg):
    from iotml.core.normalize import CAR_NORMALIZER, FULL_NORMALIZER

    return FULL_NORMALIZER if cfg["assumed"]["normalization"] == "full" \
        else CAR_NORMALIZER


class Trainer:
    """`cli.live train` as it builds the service (cli/live.py:273-300)."""

    def __init__(self, run):
        from iotml.train.artifacts import ArtifactStore
        from iotml.train.live import ContinuousTrainer

        job = run.cfg["job"]
        self.store = ArtifactStore(run.path("artifacts"))
        self.svc = ContinuousTrainer(
            run.broker, run.cfg["deployment"]["topic"], self.store,
            batch_size=job["batch_size"], take_batches=job["take_batches"],
            epochs_per_round=job["epochs"], normalizer=_normalizer(run.cfg))
        self.consumer = self.svc.consumer
        self.min_available = self.svc.min_available
        self.group = self.svc.group

    def batcher(self):
        return self.svc.batches

    def set_batcher(self, b):
        self.svc.batches = b

    def seed_weights(self, params, sample_x):
        t = self.svc.trainer
        t._ensure_state(sample_x)
        t.state = t.state.replace(params=params)

    def state(self):
        s = self.svc.trainer.state
        adam = s.opt_state[0]
        return jax.device_get((s.params, adam.mu, adam.nu))

    def wrap(self, span):
        """The benchmark's spans around the calls into each layer."""
        self.svc.train_round = span("bench.round", self.svc.train_round)
        self.svc.trainer.fit_compiled = span(
            "bench.fit_compiled", keep_losses(
                self, self.svc.trainer.fit_compiled))
        self.svc.publish = span("bench.publish", self.svc.publish)
        self.consumer.commit = span("bench.commit", self.consumer.commit)

    def run(self, stop, on_round):
        return self.svc.run(stop=stop, on_round=on_round)

    def artifacts_ok(self, rounds: int) -> bool:
        name = self.svc.model_name
        return all(self.store.exists(f"{name}.r{k}")
                   for k in range(1, rounds + 1)) and \
            self.store.get_text(f"{name}.latest") == f"{name}.r{rounds}"


class Scorer:
    """`cli.live score` as it builds the service (cli/live.py:301-314)."""

    def __init__(self, run, params):
        from iotml.models.h5_export import autoencoder_params_to_h5
        from iotml.serve.live import LiveScorer
        from iotml.train.artifacts import ArtifactStore

        dep, sc = run.cfg["deployment"], run.cfg["scorer"]
        store = ArtifactStore(run.path("artifacts"))
        # the artifact path a user's scorer takes: an h5 blob + the pointer
        local = run.path("seed-model.h5")
        autoencoder_params_to_h5(jax.tree.map(np.asarray, params), local)
        store.upload(local, "cardata-live.h5.r1")
        store.put_text("cardata-live.h5.latest", "cardata-live.h5.r1")
        self.svc = LiveScorer(run.broker, dep["topic"],
                              dep["predictions_topic"], store,
                              batch_size=sc["batch_size"],
                              car_feature_heads=sc["car_feature_heads"],
                              normalizer=_normalizer(run.cfg))
        self.svc.wait_for_model(10.0)
        self.svc.scorer.warm_buckets((self.svc.model.input_dim,))
        self.consumer = self.svc.scorer.batches.consumer
        self.group = self.consumer.group

    def batcher(self):
        return self.svc.scorer.batches

    def set_batcher(self, b):
        self.svc.scorer.batches = b

    def scored(self) -> int:
        return self.svc.scorer.scored

    def wrap(self, span):
        s = self.svc.scorer
        s.score_available = span("bench.drain", s.score_available)
        s._score_super_batch = span("bench.super_batch",
                                    s._score_super_batch)
        s.out.flush = span("bench.flush", s.out.flush)
        self.consumer.commit = span("bench.commit", self.consumer.commit)
        self.svc.maybe_swap = span("bench.model_poll", self.svc.maybe_swap)

    def run(self, stop, on_drain):
        return self.svc.run(stop=stop, on_drain=on_drain)

    def finish_drain(self) -> int:
        """Score whatever is still on the log until a drain completes
        (the commit point); returns rows scored."""
        n = 0
        while True:
            k = self.svc.scorer.score_available()
            n += k
            if k == 0:
                return n

    @staticmethod
    def parse(payload: bytes) -> np.ndarray:
        """One prediction payload → its 18 numbers."""
        body = payload.split(b"|", 1)[0].strip()
        if not (body.startswith(b"[") and body.endswith(b"]")):
            raise ValueError(f"not a prediction row: {payload[:60]!r}")
        return np.array(body[1:-1].split(), np.float64)
