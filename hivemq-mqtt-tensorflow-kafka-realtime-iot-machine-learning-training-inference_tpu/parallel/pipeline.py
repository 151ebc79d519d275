"""Pipeline parallelism: transformer blocks staged over a 'pipe' mesh axis.

The reference's only pipeline is systems-level — the train-pod → GCS →
predict-deployment handoff (SURVEY §2.7, reference `AUTOENCODER.../run.sh`).
The TPU rebuild makes in-model pipeline parallelism a first-class axis so
deep SensorFormer stacks can span chips whose HBM one stage's activations
would exhaust.

Design (GPipe-style, XLA-native):
- The layer stack is stored *stacked*: every block's params get a leading
  [num_layers] axis, sharded `P('pipe')`, so each device materializes only
  its own layers — this is the memory win.
- The schedule is a single `lax.scan` over M + S - 1 ticks inside
  `shard_map`.  Each tick every stage applies its blocks to its resident
  microbatch, then a `lax.ppermute` ring-shifts activations to the next
  stage over ICI.  Stage 0 injects microbatch t at tick t; the last stage
  banks its result.
- Backward is not hand-written: `jax.grad` transposes the scan and the
  ppermute (reverse ring) automatically, yielding the usual 1F1B-equivalent
  dataflow with microbatch gradient accumulation for free.
- Embed / final-norm / head are tiny; they run replicated on every stage and
  their cotangents are psum'd by the shard_map transpose, avoiding the
  heterogeneous first/last-stage params that make hand-rolled pipelines
  brittle.

Bubble fraction is (S-1)/(M+S-1) — pick n_microbatches >= 4*pipe for >80%
utilization; at demo scale the point is the compiled schedule, not the
bubble.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..train.loop import TrainState


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def pipeline_schedule(stage_fn: Callable, stage_params, mbs,
                      axis: str = "pipe"):
    """Run `stage_fn(stage_params, x)` as a pipeline over mesh axis `axis`.

    Call *inside* shard_map. `stage_params` is this device's local stage
    slice; `mbs` is [M, ...microbatch shape...], identical on every stage.
    Returns [M, ...] outputs, replicated across the axis (one psum).
    """
    n = jax.lax.psum(1, axis)  # static under shard_map
    idx = jax.lax.axis_index(axis)
    M = mbs.shape[0]
    ticks = M + n - 1

    def tick(carry, t):
        buf, outs = carry
        inj = jax.lax.dynamic_index_in_dim(
            mbs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        cur = jnp.where(idx == 0, inj, buf)
        out = stage_fn(stage_params, cur)
        w = t - (n - 1)  # microbatch the last stage finished this tick
        banked = jax.lax.dynamic_update_index_in_dim(
            outs, out, jnp.clip(w, 0, M - 1), axis=0)
        valid = (idx == n - 1) & (w >= 0)
        outs = jnp.where(valid, banked, outs)
        buf = jax.lax.ppermute(out, axis, _ring_perm(n))
        return (buf, outs), None

    carry0 = (jnp.zeros_like(mbs[0]), jnp.zeros_like(mbs))
    (_, outs), _ = jax.lax.scan(tick, carry0, jnp.arange(ticks))
    # only the last stage holds real outputs; psum replicates them ring-wide
    return jax.lax.psum(jnp.where(idx == n - 1, outs, 0.0), axis)


def pipeline_apply(stage_fn: Callable, mesh: Mesh, axis: str = "pipe"):
    """shard_map wrapper: (stacked_params, mbs) -> [M, ...] outputs.

    stacked_params leaves have leading dim = total layers, sharded over
    `axis`; every other mesh axis sees them replicated.  mbs is replicated.
    """
    def body(stacked_local, mbs):
        return pipeline_schedule(stage_fn, stacked_local, mbs, axis)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)


# ---------------------------------------------------------------------------
# SensorFormer pipeline-parallel training
# ---------------------------------------------------------------------------

def stack_blocks(params: dict, num_layers: int):
    """Split SensorFormer params into (static, blocks) where blocks leaves
    carry a leading [num_layers] stacking axis (shardable over 'pipe')."""
    static = {k: v for k, v in params.items() if not k.startswith("block")}
    blocks = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[params[f"block{i}"] for i in range(num_layers)])
    return static, blocks


def unstack_blocks(static: dict, blocks, num_layers: int) -> dict:
    params = dict(static)
    for i in range(num_layers):
        params[f"block{i}"] = jax.tree.map(lambda a, i=i: a[i], blocks)
    return params


def make_pp_train_step(model, tx, mesh: Mesh, n_microbatches: int,
                       data_axis: str = "data", pipe_axis: str = "pipe"):
    """Build (init_fn, step_fn, put_x) for pipeline(+data)-parallel training
    of a SensorFormer on the next-step objective.

    Mesh is (data_axis, pipe_axis): batch rows shard over data, the layer
    stack shards over pipe.  `model.num_layers` must divide by the pipe size
    and the per-data-shard batch by n_microbatches.

    state.params = {'static': embed/pos/ln_f/head (replicated),
                    'blocks': stacked [L, ...] leaves (sharded P(pipe))}.
    """
    import flax.linen as nn

    from ..models.transformer import Block

    n_pipe = mesh.shape[pipe_axis]
    L = model.num_layers
    if L % n_pipe:
        raise ValueError(f"num_layers={L} not divisible by pipe={n_pipe}")
    if model.attn_mode == "ring":
        # ring attention needs a 'seq' axis; each pipeline stage sees the
        # full sequence, so there is nothing to ring over
        raise ValueError("attn_mode='ring' cannot compose with pipeline "
                         "parallelism; use 'dense' or 'flash' (full T per "
                         "stage) or train via make_sp_train_step")
    per_stage = L // n_pipe
    block = Block(model.d_model, model.num_heads, attn_mode=model.attn_mode)
    embed = nn.Dense(model.d_model, name="embed")
    pos = nn.Embed(model.max_len, model.d_model, name="pos")
    ln_f = nn.LayerNorm(name="ln_f")
    head = nn.Dense(model.features, name="head")

    def stage_fn(blocks_local, h):
        # blocks_local leaves: [per_stage, ...] — this stage's layer slice
        for j in range(per_stage):
            p = jax.tree.map(lambda a, j=j: a[j], blocks_local)
            h = block.apply({"params": p}, h)
        return h

    def local_loss(static, blocks_local, x_local):
        Bl, T, F = x_local.shape
        h = embed.apply({"params": static["embed"]}, x_local)
        h = h + pos.apply({"params": static["pos"]}, jnp.arange(T))
        mbs = h.reshape(n_microbatches, Bl // n_microbatches, T, model.d_model)
        outs = pipeline_schedule(stage_fn, blocks_local, mbs, pipe_axis)
        h = outs.reshape(Bl, T, model.d_model)
        pred = head.apply({"params": static["head"]},
                          ln_f.apply({"params": static["ln_f"]}, h))
        # next-step MSE; count the loss only on the last pipe stage so the
        # replicated head/embed work on other stages contributes no gradient
        se = jnp.sum(jnp.square(pred[:, :-1] - x_local[:, 1:]))
        idx = jax.lax.axis_index(pipe_axis)
        n = jax.lax.psum(1, pipe_axis)
        se = jnp.where(idx == n - 1, se, 0.0)
        cnt = jnp.where(idx == n - 1, jnp.float32(pred[:, :-1].size), 0.0)
        se_tot = jax.lax.psum(se, (data_axis, pipe_axis))
        cnt_tot = jax.lax.psum(cnt, (data_axis, pipe_axis))
        return se_tot / cnt_tot

    x_spec = P(data_axis)
    loss_fn = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(), P(pipe_axis), x_spec), out_specs=P(),
        check_vma=False)

    blocks_sharding = NamedSharding(mesh, P(pipe_axis))
    rep = NamedSharding(mesh, P())

    def shard_pp_params(params):
        return {
            "static": jax.device_put(params["static"], rep),
            "blocks": jax.tree.map(
                lambda a: jax.device_put(a, blocks_sharding),
                params["blocks"]),
        }

    def init(rng, sample_x):
        dense = model.clone(attn_mode="dense")
        raw = dense.init(rng, jnp.asarray(sample_x))["params"]
        static, blocks = stack_blocks(raw, L)
        params = shard_pp_params({"static": static, "blocks": blocks})
        opt_state = tx.init(params)  # moments inherit the params' shardings
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state, apply_fn=model.apply, tx=tx)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, x):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p["static"], p["blocks"], x))(state.params)
        updates, opt_state = state.tx.update(grads, state.opt_state,
                                             state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), {"loss": loss}

    def put_x(x):
        return jax.device_put(x, NamedSharding(mesh, x_spec))

    return init, step, put_x
