"""sensorformer-ouro-2.6b: the plain reference, and the adapter that
runs the fit leg of `run_streaming_app`'s train mode (cli/_app.py) as
`cli/lstm.py` instantiates it, with the program's `SensorHybrid` at the
source's widths, looped, as `make_model`, job after job.

The reference: the looped stack's equations as the configuration's file
has them in words, in `jax.numpy`, float32 — written here from those
equations and sharing nothing with `iotml/models/`:

- a block is `a = h + N2(Attn(N1(h)))`, `h' = a + N4(Mlp(N3(a)))`: four
  weight-only RMSNorms, one ahead of each part and one on each part's
  OUTPUT; `Attn` sixteen heads of 128 with rotary positions written out
  (neighbouring pairs, the program's pairing: the file says why that is
  the family's up to one fixed permutation), plain causal softmax
  attention ONE HEAD and 1,024 queries at a time so that T = 8,192
  fits; `Mlp` the gated SiLU;
- THE SAME ARRAYS ARE APPLIED IN FOUR PYTHON-LEVEL PASSES — no scan, no
  stacked residuals: the list of blocks is walked `total_ut_steps`
  times, the final norm closes every pass, and the one head and the one
  gate read every pass's output;
- the exit distribution as cumulative products of `1 − λ`, the last
  pass taking what is left; the objective `Σ_t p_t ℓ_t − β H(p)` as a
  masked mean; `jax.grad` of it; Adam written out.

So that one job's forward and backward fit one chip beside nothing,
every block application is recomputed in the backward pass
(`jax.checkpoint`: 24 inputs of 64 MiB are held, not 24 blocks'
activations), the attention's score blocks likewise, and the fit
donates its parameters.  A chip holds the reference's state or the
trainer's, not both: the adapter's trainer gives its device state up
first, and the four passes' losses and exit masses its first job
reported are compared with the reference's here.  Imports nothing of
the program but in the adapter.
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
    "bench_sensorformer_granite_for_ouro", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "sensorformer-granite-4.0-h-micro.py"))
_gh = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_gh)
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


def _layers() -> int:
    kinds = CFG["layer_types"][:CFG["num_hidden_layers"]]
    if set(kinds) != {"full_attention"} \
            or len(kinds) != CFG["num_hidden_layers"]:
        raise ValueError(f"layer_types {kinds}: every layer of this family "
                         f"is full_attention")
    return len(kinds)


# ------------------------------------------------------------ reference
def _init(key):
    d, f, mlp = CFG["hidden_size"], CFG["model"]["features"], \
        CFG["intermediate_size"]
    heads, kv, hd = CFG["num_attention_heads"], \
        CFG["num_key_value_heads"], CFG["head_dim"]
    if heads != kv:
        raise ValueError("this family's heads are not grouped")
    keys = iter(jax.random.split(key, 6 * _layers() + 3))

    def kernel(*shape):
        return {"kernel": STD * jax.random.normal(next(keys), shape,
                                                  jnp.float32)}

    def dense(fi, fo):
        return dict(kernel(fi, fo), bias=jnp.zeros((fo,), jnp.float32))

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32)}

    # the tree the program's flax module builds (models/hybrid.py)
    out = {"embed": dense(f, d), "head": dense(d, f), "norm_f": norm(),
           "exit_gate": dense(d, 1)}
    for i in range(_layers()):
        out[f"layer{i}"] = {
            "norm1": norm(), "post_norm1": norm(),
            "norm2": norm(), "post_norm2": norm(),
            "mixer": {"q": kernel(d, heads * hd), "k": kernel(d, heads * hd),
                      "v": kernel(d, heads * hd), "o": kernel(heads * hd, d)},
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


def _rotary(x):
    """x [B, T, H, R]: features (2i, 2i+1) turned by t · θ^(−2i/R)."""
    T, R = x.shape[1], x.shape[-1]
    inv = 1.0 / (CFG["rope_theta"] ** (np.arange(0, R, 2) / R))
    angle = jnp.asarray(np.arange(T)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2].astype(jnp.float32), \
        x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(p, u):
    """Sixteen heads, each its own keys and values; a head and a block
    of queries at a time."""
    B, T, _ = u.shape
    heads, hd = CFG["num_attention_heads"], CFG["head_dim"]
    q = _rotary((u @ p["q"]["kernel"]).reshape(B, T, heads, hd))
    k = _rotary((u @ p["k"]["kernel"]).reshape(B, T, heads, hd))
    v = (u @ p["v"]["kernel"]).reshape(B, T, heads, hd)
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    pos_k = jnp.arange(T)

    @jax.checkpoint
    def block(qb, kh, vh, start):              # [B, blk, D], [B, T, D]
        s = jnp.einsum("bqd,bkd->bqk", qb, kh) \
            * jnp.asarray(1.0 / math.sqrt(hd), qb.dtype)
        causal = (start + jnp.arange(blk))[:, None] >= pos_k[None, :]
        s = jnp.where(causal, s.astype(jnp.float32), -1e30)
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(s, axis=-1).astype(vh.dtype), vh)

    def head(args):
        qh, kh, vh = args                      # [B, T, D]
        qb = jnp.moveaxis(qh.reshape(B, T // blk, blk, hd), 1, 0)
        o = jax.lax.map(lambda a: block(a[0], kh, vh, a[1]),
                        (qb, jnp.arange(T // blk) * blk))
        return jnp.moveaxis(o, 0, 1).reshape(B, T, hd)

    o = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2).reshape(B, T, heads * hd) @ p["o"]["kernel"]


def _mlp(p, u):
    gate, up = jnp.split(u @ p["mlp_in"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["mlp_out"]["kernel"]


@jax.checkpoint
def _block(p, h):
    a = h + _rms_norm(p["post_norm1"],
                      _attention(p["mixer"], _rms_norm(p["norm1"], h)))
    return a + _rms_norm(p["post_norm2"], _mlp(p, _rms_norm(p["norm2"], a)))


def _passes(params, x):
    """→ (every pass's output [B, T, F], every pass's gate λ [B, T]):
    the same blocks walked `total_ut_steps` times."""
    s = x @ params["embed"]["kernel"] + params["embed"]["bias"]
    outputs, gates = [], []
    for _ in range(CFG["total_ut_steps"]):
        for i in range(_layers()):
            s = _block(params[f"layer{i}"], s)
        s = _rms_norm(params["norm_f"], s)
        outputs.append(s @ params["head"]["kernel"] + params["head"]["bias"])
        logit = s @ params["exit_gate"]["kernel"] + params["exit_gate"]["bias"]
        gates.append(jax.nn.sigmoid(logit[..., 0].astype(jnp.float32)))
    return outputs, gates


def _exit_distribution(gates):
    """p_t = λ_t ∏_{j<t} (1 − λ_j) for t < R; the last pass takes what
    is left."""
    left, p = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return p + [left]


def _objective(params, x, y, mask):
    """→ (loss, (the passes' mean losses [R], mean exit masses [R]))."""
    outputs, gates = _passes(params, x)
    p = _exit_distribution(gates)
    losses = [jnp.mean(jnp.square(out - y), axis=-1).astype(jnp.float32)
              for out in outputs]                       # [B, T] each
    expected = sum(pt * lt for pt, lt in zip(p, losses))
    entropy = -sum(pt * jnp.log(jnp.maximum(pt, 1e-30)) for pt in p)
    m = mask[:, None].astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(m) * x.shape[1], 1.0)

    def mean(v):
        return jnp.sum(v * m) / denom

    loss = mean(expected - CFG["model"]["beta"] * entropy)
    return loss, (jnp.stack([mean(v) for v in losses]),
                  jnp.stack([mean(v) for v in p]))


def forward(params, x):
    """The prediction the trainer reports: the last pass's."""
    return _passes(params, x)[0][-1]


def loss_fn(params, x, y, mask, operands=None):
    assert operands is None
    return _objective(params, x, y, mask)[0]


def make_fit(loss, epochs: int):
    """One job as the configuration states it: `epochs` passes over the
    same batches, Adam after every batch.  Returns (params, mu, nu,
    per-epoch mean loss), and holds the passes' losses and exit masses
    to the program's first job (`_hold_passes`: a check of the run
    where a trainer ran; else the control's lower precision against the
    reference's own first fit, printed).  The program donates its copy
    of the parameters handed in, which stay the caller's."""
    if loss is not loss_fn:
        raise ValueError("this configuration's fit follows its own loss")
    program, check, said = None, None, []
    for t in list(_TRAINERS):
        program, check = t.first_reports, t.check
        t.release()
    lr = CFG["model"]["optimizer"]["learning_rate"]

    def fit(params, xs, ys, masks):
        dt = jax.tree.leaves(params)[0].dtype
        zeros = jax.tree.map(jnp.zeros_like, params)

        def step(carry, inp):
            p, mu, nu, t = carry
            x, y, m = inp
            (val, passes), g = jax.value_and_grad(
                _objective, has_aux=True)(p, x, y, m)
            t = t + 1
            mu = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, mu, g)
            nu = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, nu, g)
            c1 = (1 - B1 ** t).astype(dt)
            c2 = (1 - B2 ** t).astype(dt)
            p = jax.tree.map(
                lambda w, a, b: w - (lr * (a / c1)
                                     / (jnp.sqrt(b / c2) + EPS)).astype(dt),
                p, mu, nu)
            return (p, mu, nu, t), (val, passes)

        def epoch(carry, _):
            carry, (vals, passes) = jax.lax.scan(step, carry,
                                                 (xs, ys, masks))
            return carry, (jnp.mean(vals.astype(jnp.float32)), passes)

        (p, mu, nu, _), (losses, passes) = jax.lax.scan(
            epoch, (params, zeros, zeros, jnp.zeros((), jnp.float32)),
            None, length=epochs)
        return p, mu, nu, losses, passes

    donating = jax.jit(fit, donate_argnums=(0,))

    def run(params, *batches):
        *state, passes = donating(jax.tree.map(jnp.array, params), *batches)
        said.append(tuple(np.asarray(v, np.float64) for v in passes))
        _hold_passes(said[0], said[-1] if program is None and len(said) > 1
                     else program, check)
        return tuple(state)

    return run


def _hold_passes(reference, other, check) -> None:
    """The job's per-pass losses and exit masses, `[epochs, steps,
    passes]` each, by the reference and by the other side (the program's
    first job, or the control's lower precision), and the gaps between
    them AT THE JOB'S FIRST STEP, where both sides hold the same
    weights: what the four passes, the gate and the exit distribution
    compute — the losses' by the largest relative gap, the masses' by
    the largest absolute one (they sum to 1).  From the second step on
    the two sides' weights differ by their own rounding, which the
    epochs' losses and the update's norms hold; those steps' gaps are
    printed.  A side that ran another number of passes, or a trainer
    that reported none, cannot be compared: its gap is infinite.  A
    check of the run where `check` is given."""
    ref_loss, ref_mass = reference

    def said(side, loss, mass):
        print(f"passes, {side}: first step's loss",
              [float(f"{v:.6g}") for v in loss[0, 0]], "exit mass",
              [float(f"{v:.6g}") for v in mass[0, 0]], "| the job's mean "
              "loss", [float(f"{v:.6g}") for v in loss.mean(axis=(0, 1))],
              "exit mass",
              [float(f"{v:.6g}") for v in mass.mean(axis=(0, 1))],
              flush=True)

    said("reference", ref_loss, ref_mass)
    if other is None and check is None:
        return
    gaps = {"pass_loss_gap": math.inf, "exit_mass_gap": math.inf}
    if other is not None:
        loss, mass = (np.asarray(v, np.float64) for v in other)
        said("other side", loss, mass)
        if loss.shape == ref_loss.shape:
            by_step = np.abs(loss - ref_loss) / np.abs(ref_loss)
            print("passes, largest gaps a step: loss",
                  [float(f"{v:.3g}") for v in by_step.max(axis=-1).ravel()],
                  "exit mass", [float(f"{v:.3g}") for v in np.abs(
                      mass - ref_mass).max(axis=-1).ravel()], flush=True)
            gaps = {"pass_loss_gap": float(by_step[0, 0].max()),
                    "exit_mass_gap": float(
                        np.abs(mass - ref_mass)[0, 0].max())}
    for name, gap in gaps.items():
        if check is not None:
            check(name, gap, CFG["limits"]["train"][name])
        else:
            print(f"read  {name}: {gap!r}", flush=True)


# -------------------------------------------------------------- adapter
def hybrid_config(cfg: dict):
    """The program's `HybridConfig` of a configuration file."""
    from iotml.models.hybrid import HybridConfig

    if "loop_steps" not in {f.name for f in
                            dataclasses.fields(HybridConfig)}:
        raise SystemExit(
            "this checkout's program has no looped stack: no passes over "
            "one set of layers, no norms on a part's output, no exit gate "
            "and no objective of a model's own (iotml/models/hybrid.py): "
            "it cannot run sensorformer-ouro-2.6b")
    use(cfg)
    return HybridConfig(
        d_model=cfg["hidden_size"],
        layer_types=("attention",) * _layers(),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attention_multiplier=cfg["head_dim"] ** -0.5,
        attn_rope_theta=float(cfg["rope_theta"]),
        mlp_dim=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        loop_steps=cfg["total_ut_steps"], post_norms=True,
        exit_entropy_weight=cfg["model"]["beta"],
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0)


class Trainer(_gh.Trainer):
    """The hybrid configuration's adapter — the fit leg of
    `run_streaming_app`'s train mode, job after job on one Trainer and
    one cursor, no job storing a checkpoint or committing, able to give
    the chip back — around the program's looped `SensorHybrid` as this
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
            self.consumer, batch_size=job["batch_size"],
            take=job["take_batches"], window=job["window"],
            only_normal=False)
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
        #: the first job's per-pass losses and exit masses, [epochs,
        #: steps, passes] each, and the run's `check`, which the
        #: reference holds them to
        self.first_reports = None
        self.check = run.check
        _TRAINERS.add(self)

    def _watch_reports(self, fit):
        def fitted(*args, **kw):
            history = fit(*args, **kw)
            said = history.get("reports", {}).get("objective")
            if said is not None and self.first_reports is None:
                self.first_reports = (np.asarray(said["pass_loss"]),
                                      np.asarray(said["exit_mass"]))
            return history
        return fitted
