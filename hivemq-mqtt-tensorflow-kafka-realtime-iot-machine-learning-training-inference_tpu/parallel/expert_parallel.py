"""Expert parallelism: MoE expert weights sharded over an 'expert' mesh axis.

Tokens shard over the batch (data × expert product), expert FFN weights
shard over 'expert', and two `lax.all_to_all`s inside the MoE layer
(`models/moe.py`) exchange token slots expert-major and back over ICI —
the GShard dispatch pattern, compiled by XLA.

Absent from the reference (SURVEY §2.7: EP "Absent — N/A"); provided here
because a fleet-scale SensorFormer is the natural place experts pay off and
the mesh/axis design must reserve the axis from day one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..train.loop import TrainState

EXPERT_LEAVES = ("w1", "b1", "w2", "b2")


def expert_param_specs(params, ep_axis: str = "expert"):
    """Spec tree: MoE expert weights shard their leading [E] dim over the
    expert axis; router and everything else replicate."""
    def spec(path, leaf):
        names = [p.key if hasattr(p, "key") else str(p) for p in path]
        if "moe" in names and names[-1] in EXPERT_LEAVES:
            return P(ep_axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def shard_expert_params(params, mesh: Mesh, ep_axis: str = "expert"):
    specs = expert_param_specs(params, ep_axis)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def make_ep_train_step(model, tx, mesh: Mesh, data_axis: str = "data",
                       ep_axis: str = "expert", aux_weight: float = 0.01):
    """Build (init_fn, step_fn, put_x) for expert(+data)-parallel training
    of a MoESensorFormer on the next-step objective.

    Mesh is (data_axis, ep_axis). Batch rows shard over the *product* of
    both axes (every device works a token slice); expert weights shard over
    ep_axis; the model's internal all_to_alls ride the ep axis.
    """
    ep_model = model.clone(ep_axis=ep_axis)
    x_spec = P((data_axis, ep_axis))

    def local_loss(params, x_local):
        pred, aux = ep_model.apply({"params": params}, x_local)
        se = jnp.sum(jnp.square(pred[:, :-1] - x_local[:, 1:]))
        cnt = jnp.float32(pred[:, :-1].size)
        se_tot = jax.lax.psum(se, (data_axis, ep_axis))
        cnt_tot = jax.lax.psum(cnt, (data_axis, ep_axis))
        n_shards = jax.lax.psum(1, (data_axis, ep_axis))
        aux_mean = jax.lax.psum(aux, (data_axis, ep_axis)) / n_shards
        mse = se_tot / cnt_tot
        return mse + aux_weight * aux_mean, mse

    def init(rng, sample_x):
        dense = model.clone(ep_axis=None)
        raw = dense.init(rng, jnp.asarray(sample_x))["params"]
        params = shard_expert_params(raw, mesh, ep_axis)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           opt_state=tx.init(params),
                           apply_fn=model.apply, tx=tx)
        return state

    def build_loss(params):
        specs = expert_param_specs(params, ep_axis)
        return jax.shard_map(
            local_loss, mesh=mesh,
            in_specs=(specs, x_spec), out_specs=(P(), P()),
            check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, x):
        loss_fn = build_loss(state.params)
        (loss, mse), grads = jax.value_and_grad(
            lambda p: loss_fn(p, x), has_aux=True)(state.params)
        updates, opt_state = state.tx.update(grads, state.opt_state,
                                             state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), {"loss": loss, "mse": mse}

    def put_x(x):
        return jax.device_put(x, NamedSharding(mesh, x_spec))

    return init, step, put_x
