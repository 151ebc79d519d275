"""Sequence-parallel training: shard the time axis, train on full context.

Composes with data parallelism over a ('data', 'seq') mesh: batch rows shard
over 'data', the sequence dimension shards over 'seq', attention runs as
ring attention (KV rotating over ICI), and the next-step objective's
cross-shard coupling — position t's target x[t+1] lives on the next shard
for the shard-final step — is a single `ppermute` neighbor exchange.
Gradients of all collectives are handled by their transpose rules, so the
whole step is `jax.grad` of one shard_mapped loss.

This is the long-context training path the reference never had (its LSTM
trains at look_back=1, batch 1 — SURVEY §2.5); here a 100k-step per-car
history trains without any chip holding the full sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..train.loop import TrainState


def shift_in_next(x_local, axis_name: str):
    """For each local [B, Tl, F] shard, return the next-step targets
    [B, Tl, F]: rows 0..Tl-2 come from the local shard, row Tl-1 is the
    first row of the *next* shard (garbage on the final shard — mask it)."""
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i - 1) % n) for i in range(n)]
    head_of_next = jax.lax.ppermute(x_local[:, :1], axis_name, perm)
    return jnp.concatenate([x_local[:, 1:], head_of_next], axis=1)


def next_step_mask(Tl: int, axis_name: str):
    """[Tl] validity mask for next-step targets: all 1 except the global
    final timestep (which has no successor)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    local_pos = jnp.arange(Tl)
    is_global_last = (my == n - 1) & (local_pos == Tl - 1)
    return jnp.where(is_global_last, 0.0, 1.0)


def make_sp_train_step(model, tx, mesh: Mesh, data_axis: str = "data",
                       seq_axis: str = "seq"):
    """Build (init_fn, step_fn) for sequence+data-parallel training of a
    SensorFormer-like model (attn_mode='ring', ring_axis=seq_axis).

    step_fn(state, x) with x: [B, T, F] sharded P(data, seq); returns
    (state, metrics) with replicated params/grads (psum over both axes).
    """

    x_spec = P(data_axis, seq_axis)

    def local_loss(params, x_local):
        B, Tl, F = x_local.shape
        my = jax.lax.axis_index(seq_axis)
        n = jax.lax.psum(1, seq_axis)  # static: mesh axis size
        max_len = getattr(model, "max_len", None)
        if max_len is not None and n * Tl > max_len:
            raise ValueError(
                f"global sequence {n * Tl} exceeds model.max_len={max_len}; "
                f"the position Embed gather would silently clamp under jit")
        positions = my * Tl + jnp.arange(Tl)
        pred = model.apply({"params": params}, x_local, positions=positions)
        target = shift_in_next(x_local, seq_axis)
        mask = next_step_mask(Tl, seq_axis)[None, :, None]
        se = jnp.sum(jnp.square(pred - target) * mask)
        se_tot = jax.lax.psum(se, (data_axis, seq_axis))
        # elements counted: valid local steps × local batch × features
        cnt_tot = jax.lax.psum(jnp.sum(mask) * B * F, (data_axis, seq_axis))
        return se_tot / cnt_tot

    loss_fn = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(), x_spec), out_specs=P(),
        check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, x):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, x))(state.params)
        updates, opt_state = state.tx.update(grads, state.opt_state,
                                             state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), {"loss": loss}

    def init(rng, sample_x):
        # params don't depend on the attention mode; init with the dense
        # twin so tracing needn't run inside shard_map
        dense = model.clone(attn_mode="dense")
        state = TrainState.create(dense, rng, jnp.asarray(sample_x), tx=tx)
        # replicate params/opt state across the mesh
        rep = NamedSharding(mesh, P())
        return state.replace(
            params=jax.device_put(state.params, rep),
            opt_state=jax.device_put(state.opt_state, rep))

    def put_x(x):
        return jax.device_put(x, NamedSharding(mesh, x_spec))

    return init, step, put_x


def sp_next_step_loss_reference(model_dense, params, x):
    """Single-device oracle: same masked next-step loss, dense attention."""
    pred = model_dense.apply({"params": params}, x)
    se = jnp.sum(jnp.square(pred[:, :-1] - x[:, 1:]))
    cnt = pred[:, :-1].size
    return se / cnt
