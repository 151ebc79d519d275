"""jit-compiled micro-batch streaming training.

The reference trains with `autoencoder.fit(dataset, epochs=20)` over a
batched Kafka stream (cardata-v3.py:220-222): micro-batch streaming
ingestion, *not* online learning (reference README.md:130-140) — every epoch
re-reads the topic from the start offset.

TPU-first translation:
- one `jax.jit` train step, donated state, fixed [B, F] shapes (padded tails
  carry a validity mask so the step never recompiles);
- loss = masked MSE + Keras activity-regularizer penalty (models/autoencoder);
- the Keras `accuracy` metric quirk (elementwise equality on a regression —
  what `metrics=['accuracy']` resolves to under MSE loss) is reproduced so
  history dicts match the reference logs' shape;
- epochs iterate the *stream* via `SensorBatches.epochs`, preserving the
  re-read-from-offset semantics.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict

from ..obs import metrics as obs_metrics
from ..obs import tracing


@struct.dataclass
class TrainState:
    step: jnp.ndarray
    params: FrozenDict
    opt_state: optax.OptState
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    @classmethod
    def create(cls, model, rng, sample_x, tx: Optional[optax.GradientTransformation] = None,
               learning_rate: float = 1e-3, tx_key=None):
        """Init params from a sample batch. lr 1e-3 = Keras Adam default
        (what `optimizer='adam'` means in the reference).

        Params AND optimizer state init under ONE jit (cached per
        (model, optimizer)): flax's eager init executes the full forward
        op-by-op and optax's init is an eager zeros-op per param leaf —
        each eager op is its own device dispatch, so a fresh recurrent
        Trainer paid hundreds of them before training at all (cost on
        the current chip: not measured).  `tx_key` is the hashable cache
        descriptor when the caller built the optimizer itself (a fresh
        optax object per Trainer would otherwise defeat the cache by
        identity)."""
        tx = tx or optax.adam(learning_rate)
        init = jitted_state_init(model, tx, tx_key=tx_key)
        params, opt_state = init(rng, jnp.asarray(sample_x))
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, apply_fn=model.apply, tx=tx)


def _masked_mse(pred, target, mask):
    """Mean squared error over valid rows only (mask is [B] of 0/1)."""
    per_elem = jnp.square(pred - target)
    # broadcast mask over trailing dims
    m = mask.reshape(mask.shape + (1,) * (per_elem.ndim - 1))
    denom = jnp.maximum(jnp.sum(m) * per_elem[0].size, 1.0)
    return jnp.sum(per_elem * m) / denom


def _keras_accuracy(pred, target, mask):
    m = mask.reshape(mask.shape + (1,) * (pred.ndim - 1))
    eq = (pred == target).astype(jnp.float32) * m
    return jnp.sum(eq) / jnp.maximum(jnp.sum(m) * pred[0].size, 1.0)


def make_loss_fn(model, supervised: bool = False):
    """Loss closure.  Autoencoder mode targets the input itself
    (zip(x, x), cardata-v3.py:218); supervised mode uses (x, y) windows.

    A model that names `report_collections` (variable collections its
    layers write data-dependent counts to: an expert layer's routing
    load) has them returned as a third entry of the aux, and the fits
    below carry them out beside the losses.  A model may also name its
    own `objective` over `(outputs, y, mask)` → (loss, the prediction
    the accuracy reads, what it reports) — a looped stack's expectation
    over its passes' outputs — which stands in the masked mean squared
    error's place, and whose reports ride out under `objective` beside
    the collections.  Any other model's loss is the program it always
    was."""
    reporting = list(getattr(model, "report_collections", ()))
    objective = getattr(model, "objective", None)

    def loss_fn(params, x, y, mask):
        if (reporting or objective) and supervised:
            out, reports = model.apply(
                {"params": params}, x, mutable=reporting) if reporting \
                else (model.apply({"params": params}, x), {})
            if objective is None:
                return _masked_mse(out, y, mask), (out, y, reports)
            loss, pred, said = objective(out, y, mask)
            return loss, (pred, y, {**reports, "objective": said})
        out = model.apply({"params": params}, x, with_penalty=True) \
            if not supervised else (model.apply({"params": params}, x), 0.0)
        pred, penalty = out if isinstance(out, tuple) else (out, 0.0)
        target = x if not supervised else y
        loss = _masked_mse(pred, target, mask) + penalty
        return loss, (pred, target)

    return loss_fn


def make_raw_train_step(model, tx, supervised: bool = False,
                        row_loss: bool = False):
    """Un-jitted step — `parallel.data_parallel` re-jits it with mesh
    shardings; single-chip callers use `make_train_step`.

    ``row_loss=True`` adds ``metrics["row_loss"]``: the per-row masked
    pre-update MSE ([B], padding rows 0).  Under a mesh the vector stays
    sharded over 'data', so each device's rows land back on their own
    chip — the per-chip drift-detector signal (iotml.online) at zero
    collective cost; scan paths that ignore it have it dead-code
    eliminated."""
    loss_fn = make_loss_fn(model, supervised)

    def iotml_train_step(state: TrainState, x, y, mask):
        (loss, (pred, target, *reports)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, x, y, mask)
        # the optimizer's operations carry `adam` in their metadata, as
        # the model's carry `attn`/`mlp`: a device trace tells them apart
        with jax.named_scope("adam"):
            updates, opt_state = state.tx.update(grads, state.opt_state,
                                                 state.params)
            params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "accuracy": _keras_accuracy(pred, target, mask)}
        if reports:
            metrics["reports"] = reports[0]
        if row_loss:
            per_elem = jnp.square(pred - target)
            metrics["row_loss"] = jnp.mean(
                per_elem.reshape(per_elem.shape[0], -1), axis=-1) * mask
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), metrics

    return iotml_train_step


def make_train_step(model, tx, supervised: bool = False):
    return jax.jit(make_raw_train_step(model, tx, supervised))


def make_scanned_fit(model, tx, supervised: bool = False):
    """Whole-fit-as-one-XLA-program: lax.scan over batches (inner) and
    epochs (outer), state donated, data device-resident.

    Per-step dispatch is the TPU throughput killer for small models — the
    reference's 100-row batches are microseconds of MXU work, so a
    step-per-dispatch loop is pure host/link latency.  Scanning the entire
    fit compiles once and runs N_epochs × N_batches updates in a single
    device program; numerically identical to the step loop.

    Returns (state, (losses, accs)), each [epochs]; a model that reports
    (`make_loss_fn`) adds a third entry, its reports with leading axes
    [epochs, batches], which leave the device at the same sync.
    """
    raw = make_raw_train_step(model, tx, supervised)

    def iotml_scanned_fit(state: TrainState, xs, ys, masks, epochs: int):
        def batch_step(st, inp):
            x, y, m = inp
            st, metrics = raw(st, x, y, m)
            return st, (metrics["loss"], metrics["accuracy"],
                        *([metrics["reports"]] if "reports" in metrics
                          else []))

        def epoch_step(st, _):
            st, (losses, accs, *reports) = jax.lax.scan(
                batch_step, st, (xs, ys, masks))
            return st, (jnp.mean(losses), jnp.mean(accs), *reports)

        return jax.lax.scan(epoch_step, state, None, length=epochs)

    return jax.jit(iotml_scanned_fit, static_argnames=("epochs",),
                   donate_argnums=(0,))


# The jitted programs are named `iotml_*` (the function's __name__ is what
# the HLO module, a device trace and JAX's compile events carry): the
# compile counters of utils.device key on that prefix.
#
# jax.jit caches per function object; a fresh closure per fit_compiled call
# would re-trace (and without backend caching, re-compile) every time.  Keyed
# on (model, tx identity-or-descriptor, supervised) so repeated jobs — e.g.
# periodic retrains — reuse the compiled program.
# Bounded LRU (not a bare dict): the closures hold their models strongly,
# so an unbounded cache in a long-lived process that rebuilds models per
# retrain cycle would pin every dead model and compiled program forever.
_CACHE_LIMIT = 8
_SCANNED_CACHE: OrderedDict = OrderedDict()
_EVAL_CACHE: OrderedDict = OrderedDict()
_INIT_CACHE: OrderedDict = OrderedDict()


def _lru_get(cache, key, make):
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = make()
        if len(cache) > _CACHE_LIMIT:
            cache.popitem(last=False)  # evict least-recently used
    else:
        cache.move_to_end(key)
    return fn


def adam_cached(learning_rate: float) -> optax.GradientTransformation:
    """One optax.adam object per learning rate.

    `TrainState.tx` is a static (non-pytree) field, and a fresh
    `optax.adam(lr)` builds fresh init/update closures that compare
    UNEQUAL to the last one — so every fresh Trainer used to retrace and
    recompile the scanned fit even though the program was identical
    (compile time on the current chip: not measured).  Sharing the object
    makes the static field compare equal and the compile cache hit."""
    return _lru_get(_INIT_CACHE, ("adam-tx", learning_rate),
                    lambda: optax.adam(learning_rate))


def adam_injectable_cached(learning_rate: float
                           ) -> optax.GradientTransformation:
    """Adam with RUNTIME-mutable hyperparameters (optax
    inject_hyperparams): the learning rate lives in ``opt_state
    .hyperparams`` as a traced array, so the online learner's
    drift-triggered LR boost is an opt_state edit — no retrace, no
    recompile, same jitted step.  Cached per initial rate for the same
    compile-cache reason as ``adam_cached`` (the tx object's identity
    keys the jit caches)."""
    return _lru_get(
        _INIT_CACHE, ("adam-inject-tx", learning_rate),
        lambda: optax.inject_hyperparams(optax.adam)(
            learning_rate=learning_rate))


def jitted_state_init(model, tx, tx_key=None):
    """jit-compiled (params, opt_state) init, cached per (model, tx)."""
    key = (model, tx_key if tx_key is not None else id(tx))

    def make():
        @jax.jit
        def iotml_state_init(rng, x):
            params = model.init(rng, x)["params"]
            return params, tx.init(params)

        return iotml_state_init

    return _lru_get(_INIT_CACHE, key, make)


def scanned_fit_cached(model, tx, supervised: bool, tx_key=None):
    key = (model, tx_key if tx_key is not None else id(tx), supervised)
    return _lru_get(_SCANNED_CACHE, key,
                    lambda: make_scanned_fit(model, tx, supervised))


def make_scanned_window_steps(model, tx, supervised: bool = False):
    """K sequential SGD updates as ONE device program (lax.scan),
    returning the per-window pre-update losses — the online learner's
    catch-up path.  Numerically identical to K single steps; what
    changes is dispatch: one jit call + one host→device transfer per
    GROUP instead of per window, which is the difference between the
    incremental mode meeting its throughput SLO and not (measured:
    0.62× → >1× of micro-batch train rate at K=8).  The per-window
    loss vector keeps drift detection at window granularity even
    through a fused group."""
    raw = make_raw_train_step(model, tx, supervised)

    def iotml_window_steps(state: TrainState, xs, masks):
        def step(st, inp):
            x, m = inp
            st, metrics = raw(st, x, x, m)
            return st, metrics["loss"]

        return jax.lax.scan(step, state, (xs, masks))

    return jax.jit(iotml_window_steps, donate_argnums=(0,))


def scanned_window_steps_cached(model, tx, tx_key=None):
    key = (model, tx_key if tx_key is not None else id(tx), "winscan")
    return _lru_get(_SCANNED_CACHE, key,
                    lambda: make_scanned_window_steps(model, tx))


def make_eval_step(model, supervised: bool = False):
    """jit eval closure, cached per model (bounded LRU, see
    _SCANNED_CACHE): every StreamScorer (and each serve drain in a
    restart-per-drain deployment) calls this, and a fresh jit closure per
    call would recompile the eval program on every drain."""
    def make():
        @jax.jit
        def iotml_eval_step(params, x):
            return model.apply({"params": params}, x)

        return iotml_eval_step

    return _lru_get(_EVAL_CACHE, model, make)


class Trainer:
    """model.fit for streams: epochs × batches with history, like Keras."""

    def __init__(self, model, rng=None, learning_rate: float = 1e-3,
                 supervised: bool = False, tx=None):
        self.model = model
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # tx_key: hashable descriptor for the jit cache when we built the
        # optimizer ourselves (a user-supplied tx is keyed by identity)
        self._tx_key = ("adam", learning_rate) if tx is None else None
        self.learning_rate = learning_rate
        self.tx = tx or adam_cached(learning_rate)
        self.supervised = supervised
        self.state: Optional[TrainState] = None
        self._step = None
        #: calls of fit_compiled so far: the `round` of its phase spans
        self.fits = 0

    def _ensure_state(self, sample_x):
        if self.state is None:
            # once a Trainer, and a start's second-largest part where the
            # model is large: `iotml_state_init` traced, lowered, read
            # from the cache or compiled, and run — the span ends when
            # the state is on the device, which the first fit would
            # wait for in any case
            with tracing.phase("start", "state_init"):
                self.state = jax.block_until_ready(TrainState.create(
                    self.model, self.rng, sample_x, tx=self.tx,
                    tx_key=self._tx_key))
                self._step = make_train_step(self.model, self.tx,
                                             self.supervised)

    def fit(self, batches, epochs: int = 1, verbose: bool = False,
            callbacks=()) -> dict:
        """batches: SensorBatches (or any iterable-of-Batch with .epochs).

        This is the Keras-shaped per-step loop: it re-reads the stream
        every epoch and fires callbacks per batch — but each step is one
        device dispatch for microseconds of work, so prefer
        `fit_compiled` for anything but live-stream/callback training.
        When the batch source is a frozen slice (`cache=True`) and no
        per-batch observation is requested, the two are semantically
        identical and this delegates automatically."""
        if not callbacks and not verbose and getattr(batches, "cache", False):
            return self.fit_compiled(batches, epochs)
        history = {"loss": [], "accuracy": [], "records": [], "seconds": []}
        epoch_iter = batches.epochs(epochs) if hasattr(batches, "epochs") \
            else (iter(batches) for _ in range(epochs))
        for e, it in enumerate(epoch_iter):
            t0 = time.perf_counter()
            tot_loss = tot_acc = 0.0
            n = records = 0
            for b in it:
                self._ensure_state(b.x)
                y = b.y if b.y is not None else b.x
                with obs_metrics.train_step_seconds.time():
                    self.state, m = self._step(self.state, b.x, y, b.mask)
                obs_metrics.records_trained.inc(b.n_valid)
                tot_loss += float(m["loss"])
                tot_acc += float(m["accuracy"])
                n += 1
                records += b.n_valid
                for cb in callbacks:
                    cb.on_batch_end(b, m)
            dt = time.perf_counter() - t0
            if tracing.ENABLED and hasattr(batches, "take_traces"):
                # every record decoded this epoch went through the step:
                # close with the e2e (ingest → train) span.  Epoch 2+ of a
                # stream re-read decodes the same records again — each
                # re-read is its own trace only if re-injected upstream,
                # so typically only the first epoch closes spans.
                for ctx in batches.take_traces():
                    ctx.close("train")
            history["loss"].append(tot_loss / max(n, 1))
            history["accuracy"].append(tot_acc / max(n, 1))
            history["records"].append(records)
            history["seconds"].append(dt)
            if verbose:
                print(f"epoch {e + 1}/{epochs} - loss {history['loss'][-1]:.6f} "
                      f"- {records} records - {dt:.2f}s")
        return history

    def fit_compiled(self, batches, epochs: int = 1, fused: str = "auto"
                     ) -> dict:
        """One-XLA-program fit: decode the epoch's batches once, move them to
        device, and run all epochs × batches inside a single jitted
        `lax.scan` (see `make_scanned_fit`).  Semantically identical to
        `fit` over an immutable log slice; orders of magnitude less dispatch
        overhead for small step sizes.

        fused: "auto" additionally collapses the whole fit into ONE Pallas
        kernel when the model/optimizer match `ops.fused_train`'s contract
        (the DenseAutoencoder + Adam hot path — per-step kernel dispatch
        goes away; speed-up on the current chip: not measured); "never"
        forces the scan; "always" raises if unsupported.

        The history names the fit that ran: ``fit`` is "fused" or
        "scanned", ``interpret`` whether the fused kernel ran under the
        Pallas interpreter (CPU backend only)."""
        self.fits += 1
        with tracing.phase("train", "fit", round=self.fits):
            return self._fit_compiled(batches, epochs, fused)

    def _fit_compiled(self, batches, epochs: int, fused: str) -> dict:
        """`fit_compiled` inside its `iotml.train.fit` span; each leg is
        a phase of it (`tracing.phase`: a span, a series of
        `iotml_step_seconds`, a profiler annotation)."""
        import numpy as np

        t0 = time.perf_counter()
        # Staging policy: the slice is decoded, stacked once, and shipped
        # as ONE device_put of the (xs, masks) pair — every host→device
        # transfer the program waits on has a fixed completion latency,
        # and a round's slice is small.  A chunked double-buffered
        # variant (device_put per 32 batches overlapping the stream
        # decode) exists on the multi-chip path (DevicePrefetcher).
        # Here the overlap is the consumer's: from a loop's second job
        # on, `StreamConsumer.read_ahead` fetches the NEXT job's records
        # while this one's fit runs (`SensorBatches._take_ended`), below
        # `positions()`, so the cursors a checkpoint saves and a commit
        # writes after this call are still the trained ones.  Pulling
        # the next take here, between dispatch and sync, would move them
        # past what has been trained.
        #
        # Iterate via .epochs(1) when the source has it: for a cache=True
        # SensorBatches that's what populates the replay cache (a bare
        # iter() would consume the stream without caching, and a later
        # fit over the same source would see nothing).
        it = next(batches.epochs(1)) if hasattr(batches, "epochs") \
            else iter(batches)
        with tracing.phase("train", "host_pipeline"):
            # the host leg of the round: poll + decode + batch assembly
            # all happen inside the batcher's iterator (its consumer
            # calls are `fetch`/`decode` phases of their own; windowing
            # and normalisation are this phase's self time)
            bs = list(it)
        if not bs:
            return {"loss": [], "accuracy": [], "records": [], "seconds": []}
        with tracing.phase("train", "stack"):
            xs = np.stack([b.x for b in bs])
            masks = np.stack([b.mask for b in bs])
            # autoencoder mode targets the input itself: no ys, and the
            # transferred xs is reused instead of a byte-identical copy
            ys = np.stack([b.y if b.y is not None else b.x for b in bs]) \
                if any(b.y is not None for b in bs) else None
        records = sum(b.n_valid for b in bs)
        self._ensure_state(bs[0].x)

        from ..ops import fused_train

        activity_l1 = getattr(self.model, "activity_l1", None)
        use_fused = fused != "never" and \
            fused_train.supported(self.state, self.supervised) and \
            self._tx_key is not None and \
            activity_l1 is not None and \
            xs.nbytes <= fused_train.VMEM_DATA_BUDGET_BYTES
        if fused == "always" and not use_fused:
            raise ValueError("fused fit unsupported for this model/optimizer/"
                             "slice size")
        # which fit ran rides the history: a caller that believes it is
        # on the compiled kernel can see when it is not
        interpret = use_fused and fused_train.interpret_mode()
        # device leg: transfer + compiled program + the one sync below —
        # measured through the device_get because dispatch is async and
        # the program is not "done" until the host observes its results.
        # Its three parts time the HOST's calls (no extra sync is added):
        # what the device does under each is the profiler trace's to show
        with tracing.phase("train", "device_compute"):
            with tracing.phase("train", "transfer"):
                if use_fused or ys is None:
                    xs, masks = jax.device_put((xs, masks))
                    ys = xs
                else:
                    xs, ys, masks = jax.device_put((xs, ys, masks))
            with tracing.phase("train", "dispatch"):
                reports = ()   # a reporting model's, from the scanned fit
                if use_fused:
                    self.state, losses, accs = fused_train.fused_fit(
                        self.state, xs, masks, epochs,
                        lr=self.learning_rate, l1=activity_l1,
                        interpret=interpret)
                else:
                    scanned = scanned_fit_cached(
                        self.model, self.tx, self.supervised,
                        tx_key=self._tx_key)
                    self.state, (losses, accs, *reports) = scanned(
                        self.state, xs, ys, masks, epochs)
            obs_metrics.records_trained.inc(records * epochs)
            if tracing.ENABLED and hasattr(batches, "take_traces"):
                # the whole fit ran as one device program: per-record
                # close lands here, after the scan — the e2e span
                # includes the compiled fit, which is exactly what
                # ingest-to-train means for this path
                for ctx in batches.take_traces():
                    ctx.close("train")
            with tracing.phase("train", "sync"):
                # ONE sync for both metric vectors: each device_get
                # blocks on the device, and the second would wait on
                # nothing new
                losses, accs, *reports = jax.device_get(
                    (losses, accs, *reports))
                losses, accs = np.asarray(losses), np.asarray(accs)
            if reports:
                self.model.record_reports(reports[0])
        dt = time.perf_counter() - t0
        return {"loss": losses.tolist(), "accuracy": accs.tolist(),
                "records": [records] * epochs, "seconds": [dt / epochs] * epochs,
                "fit": "fused" if use_fused else "scanned",
                "interpret": interpret,
                # what a reporting model's layers said, [epochs, batches, …]
                **({"reports": reports[0]} if reports else {})}

    def predict(self, batches, callbacks=(), params=None):
        """Batched jit inference; calls callbacks with (batch, outputs) for
        ordered write-back (the OutputCallback pattern, cardata-v3.py:243-249).
        `params` overrides trained state (e.g. weights loaded from h5/orbax)."""
        ev = make_eval_step(self.model, self.supervised)
        params = params if params is not None else self.state.params
        outs = []
        for b in batches:
            out = ev(params, b.x)
            for cb in callbacks:
                cb.on_predict_batch_end(b, out)
            outs.append(jax.device_get(out)[: b.n_valid])
        return outs
