"""Whole-training-run-in-one-kernel: Pallas fused autoencoder fit.

The reference's training job is thousands of *tiny* SGD steps — batch 100
over an 18-wide MLP (`cardata-v3.py:176-194,218`) is microseconds of MXU
work per step.  Even with the whole fit scanned into one XLA program
(`train.loop.make_scanned_fit`), each scan iteration still dispatches ~25
separate fused kernels (forward, backward, per-tensor Adam), and at ~30µs
of TPU loop overhead per kernel the job is overhead-bound, not FLOP-bound.

This module collapses the *entire fit* — every epoch, every batch: forward,
hand-derived backward, and Adam for all eight parameter tensors — into ONE
Pallas kernel.  Data (up to a few MB) and parameters live in VMEM for the
whole run; the only HBM traffic is the initial load and the final
parameter/metric write-back.  Numerics match `make_scanned_fit` (same ops,
same order, float32) to float tolerance.

Exact math replicated (see `train.loop` / `models.autoencoder`):

  h1 = tanh(x W1 + b1);  penalty = l1 * sum|h1| / B      (Keras activity reg)
  h2 = relu(h1 W2 + b2); h3 = tanh(h2 W3 + b3); out = relu(h3 W4 + b4)
  loss = sum((out-x)^2 * m) / max(sum(m)*F, 1) + penalty  (masked MSE)
  acc  = sum((out==x) * m) / max(sum(m)*F, 1)             (Keras 'accuracy')
  Adam: optax defaults b1=.9 b2=.999 eps=1e-8, bias correction at t=step+1

Supports any DenseAutoencoder geometry (18- and 30-dim variants).  On the
CPU backend the kernel runs under the Pallas interpreter so the tier-1
tests exercise the same code; everywhere else it is compiled by Mosaic
(`interpret_mode` is the one place that decides, and callers report it).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: parameter layout: (layer name, activation) in forward order
_LAYERS = ("encoder0", "encoder1", "decoder0", "decoder1")

#: The kernel maps the whole training slice into VMEM (no grid/BlockSpecs),
#: so callers must gate on data size: beyond this budget, use the scanned
#: fit, which streams batches from HBM.  ~16 MB VMEM per v5e core, minus
#: params/moments/activations headroom.
VMEM_DATA_BUDGET_BYTES = 8 * 2 ** 20


def _flatten_params(params) -> list:
    """params tree → [W1, b1, W2, b2, W3, b3, W4, b4] (forward order)."""
    out = []
    for name in _LAYERS:
        out.append(params[name]["kernel"])
        out.append(params[name]["bias"])
    return out


def _unflatten_params(flat) -> dict:
    return {name: {"kernel": flat[2 * i], "bias": flat[2 * i + 1]}
            for i, name in enumerate(_LAYERS)}


def _fit_kernel(xs_ref, mask_ref, t0_ref, *refs, n_tensors: int,
                steps_per_epoch: int, total_steps: int, lr: float, l1: float,
                b1: float, b2: float, eps: float):
    """One kernel = the whole fit.  refs layout:
    [p_in ×8, m_in ×8, v_in ×8, p_out ×8, m_out ×8, v_out ×8, losses, accs].
    State lives in the *output* refs (copied from inputs up front), so the
    fori_loop reads and writes VMEM only."""
    n3 = 3 * n_tensors
    ins, outs = refs[:n3], refs[n3:2 * n3]
    losses_ref, accs_ref = refs[2 * n3], refs[2 * n3 + 1]
    for i in range(n3):
        outs[i][...] = ins[i][...]
    p, m, v = outs[:n_tensors], outs[n_tensors:2 * n_tensors], \
        outs[2 * n_tensors:3 * n_tensors]

    batch = xs_ref.shape[1]
    feat = xs_ref.shape[2]
    n_epochs = total_steps // steps_per_epoch
    # Mosaic cannot prove alignment for scalar stores at a dynamic index,
    # so metrics accumulate into small loop-carried per-epoch vectors via a
    # one-hot mask (pure vector ops) and are stored once after the loop.
    # 2D iota: 1D iota is not lowerable on TPU.
    epoch_ids = jax.lax.broadcasted_iota(
        jnp.int32, (n_epochs, 1), 0).reshape(n_epochs)

    def body(i, carry):
        loss_acc, acc_acc = carry
        s = jax.lax.rem(i, steps_per_epoch)
        x = xs_ref[pl.ds(s, 1)].reshape(batch, feat)
        msk = mask_ref[pl.ds(s, 1)].reshape(batch, 1)

        w1, bi1 = p[0][...], p[1][...]
        w2, bi2 = p[2][...], p[3][...]
        w3, bi3 = p[4][...], p[5][...]
        w4, bi4 = p[6][...], p[7][...]

        # ---- forward (same op order as the flax model)
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        z1 = dot(x, w1) + bi1
        h1 = jnp.tanh(z1)
        z2 = dot(h1, w2) + bi2
        h2 = jnp.maximum(z2, 0.0)
        z3 = dot(h2, w3) + bi3
        h3 = jnp.tanh(z3)
        z4 = dot(h3, w4) + bi4
        out = jnp.maximum(z4, 0.0)

        denom = jnp.maximum(jnp.sum(msk) * feat, 1.0)
        # mask enters the loss LINEARLY (a per-row sample weight), matching
        # train.loop._masked_mse — for 0/1 masks this is indistinguishable
        # from masking diff, but fractional weights must not get squared
        diff = out - x
        penalty = l1 * jnp.sum(jnp.abs(h1)) / batch
        loss = jnp.sum(diff * diff * msk) / denom + penalty
        acc = jnp.sum((out == x).astype(jnp.float32) * msk) / denom

        # ---- backward (hand-derived; matches jax.grad of the above)
        dz4 = (2.0 / denom) * diff * msk * (z4 > 0.0)
        dW4 = dot(h3.T, dz4)
        db4 = jnp.sum(dz4, axis=0)
        dh3 = dot(dz4, w4.T)
        dz3 = dh3 * (1.0 - h3 * h3)
        dW3 = dot(h2.T, dz3)
        db3 = jnp.sum(dz3, axis=0)
        dh2 = dot(dz3, w3.T)
        dz2 = dh2 * (z2 > 0.0)
        dW2 = dot(h1.T, dz2)
        db2 = jnp.sum(dz2, axis=0)
        dh1 = dot(dz2, w2.T) + (l1 / batch) * jnp.sign(h1)
        dz1 = dh1 * (1.0 - h1 * h1)
        dW1 = dot(x.T, dz1)
        db1 = jnp.sum(dz1, axis=0)

        grads = (dW1, db1, dW2, db2, dW3, db3, dW4, db4)

        # ---- Adam, optax bias-correction at t = global step + 1.
        # b^t as exp(t·ln b): Mosaic has no powf lowering, exp it has.
        t = (t0_ref[0] + i + 1).astype(jnp.float32)
        c1 = 1.0 - jnp.exp(t * math.log(b1))
        c2 = 1.0 - jnp.exp(t * math.log(b2))
        for k in range(n_tensors):
            g = grads[k]
            mk = b1 * m[k][...] + (1.0 - b1) * g
            vk = b2 * v[k][...] + (1.0 - b2) * g * g
            m[k][...] = mk
            v[k][...] = vk
            p[k][...] = p[k][...] - lr * (mk / c1) / (jnp.sqrt(vk / c2) + eps)

        onehot = (epoch_ids == (i // steps_per_epoch)).astype(jnp.float32)
        return loss_acc + loss * onehot, acc_acc + acc * onehot

    zeros = jnp.zeros((n_epochs,), jnp.float32)
    losses, accs = jax.lax.fori_loop(0, total_steps, body, (zeros, zeros))
    inv = jnp.float32(1.0 / steps_per_epoch)
    losses_ref[...] = losses * inv  # per-epoch mean, like make_scanned_fit
    accs_ref[...] = accs * inv


@functools.partial(jax.jit, static_argnames=("epochs", "lr", "l1", "b1",
                                             "b2", "eps", "interpret"))
def _fused_fit(flat_p, flat_m, flat_v, t0, xs, masks, epochs: int,
               lr: float, l1: float, b1: float, b2: float, eps: float,
               interpret: bool):
    steps_per_epoch = xs.shape[0]
    total = epochs * steps_per_epoch
    n_tensors = len(flat_p)
    out_shape = (
        [jax.ShapeDtypeStruct(a.shape, a.dtype)
         for a in (*flat_p, *flat_m, *flat_v)]
        + [jax.ShapeDtypeStruct((epochs,), jnp.float32),
           jax.ShapeDtypeStruct((epochs,), jnp.float32)]
    )
    kernel = functools.partial(
        _fit_kernel, n_tensors=n_tensors, steps_per_epoch=steps_per_epoch,
        total_steps=total, lr=lr, l1=l1, b1=b1, b2=b2, eps=eps)
    t0_arr = jnp.asarray(t0, jnp.int32).reshape(1)
    res = pl.pallas_call(kernel, name="iotml_fused_fit", out_shape=out_shape,
                         interpret=interpret)(
        xs, masks, t0_arr, *flat_p, *flat_m, *flat_v)
    n3 = 3 * n_tensors
    return res[:n3], res[n3], res[n3 + 1]


def supported(state, supervised: bool) -> bool:
    """Is this TrainState the fused kernel's exact contract? (4-layer
    DenseAutoencoder params + optax.adam state, unsupervised)."""
    if supervised:
        return False
    try:
        params = state.params
        if set(params.keys()) != set(_LAYERS):
            return False
        adam_state = state.opt_state[0]
        _ = adam_state.mu, adam_state.nu, adam_state.count
    except (AttributeError, TypeError, IndexError, KeyError):
        return False
    return True


def interpret_mode() -> bool:
    """Whether the kernel runs under the Pallas interpreter: only on the
    CPU backend, where Mosaic cannot compile it.  Any other backend gets
    the compiled kernel — one Mosaic does not serve fails at lowering
    instead of running interpreted under a device's name."""
    return jax.default_backend() == "cpu"


def fused_fit(state, xs, masks, epochs: int, lr: float = 1e-3,
              l1: float = 1e-7, interpret: Optional[bool] = None
              ) -> Tuple[object, jnp.ndarray, jnp.ndarray]:
    """Run the whole fit in one Pallas kernel.

    state: TrainState (DenseAutoencoder params + optax.adam opt_state)
    xs: [S, B, F] float32 batches; masks: [S, B] float32
    interpret: None = `interpret_mode()`; pass a bool to force either.
    Returns (new_state, losses [epochs], accs [epochs]) — per-epoch means,
    the same history `make_scanned_fit` reports.
    """
    if interpret is None:
        interpret = interpret_mode()
    adam_state = state.opt_state[0]
    flat_p = _flatten_params(state.params)
    flat_m = _flatten_params(adam_state.mu)
    flat_v = _flatten_params(adam_state.nu)
    t0 = adam_state.count

    xs = jnp.asarray(xs, jnp.float32)
    masks = jnp.asarray(masks, jnp.float32)
    out_flat, losses, accs = _fused_fit(
        flat_p, flat_m, flat_v, t0, xs, masks, epochs=int(epochs),
        lr=float(lr), l1=float(l1), b1=0.9, b2=0.999, eps=1e-8,
        interpret=bool(interpret))
    n = len(flat_p)
    total = epochs * xs.shape[0]
    new_params = _unflatten_params(out_flat[:n])
    new_mu = _unflatten_params(out_flat[n:2 * n])
    new_nu = _unflatten_params(out_flat[2 * n:3 * n])
    new_adam = adam_state._replace(count=t0 + total,
                                   mu=new_mu, nu=new_nu)
    new_opt_state = (new_adam,) + tuple(state.opt_state[1:])
    new_state = state.replace(step=state.step + total,
                              params=new_params,
                              opt_state=new_opt_state)
    return new_state, losses, accs
