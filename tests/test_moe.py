"""MoE SensorFormer + expert parallelism: the expert-sharded all_to_all
path must match the single-device dense dispatch, and routing must respect
capacity with static shapes throughout."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from iotml.models.moe import MoEFFN, MoESensorFormer
from iotml.parallel.expert_parallel import (expert_param_specs,
                                            make_ep_train_step)
from iotml.parallel.mesh import make_mesh
from jax.sharding import PartitionSpec as P


def _x(B=4, T=16, F=18, seed=0):
    return np.random.default_rng(seed).normal(size=(B, T, F)).astype(np.float32)


def test_moe_ffn_shapes_and_aux():
    ffn = MoEFFN(d_model=16, num_experts=4)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 16)),
                    jnp.float32)
    params = ffn.init(jax.random.PRNGKey(0), x)["params"]
    out, aux = ffn.apply({"params": params}, x)
    assert out.shape == x.shape
    # perfectly balanced routing gives aux = 1.0; any routing >= 1.0-ish
    assert 0.5 < float(aux) < 4.0


def test_moe_capacity_drops_are_residual_passthrough():
    # capacity_factor tiny -> most tokens dropped -> their FFN output is 0
    ffn = MoEFFN(d_model=8, num_experts=2, capacity_factor=0.01)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 8)), jnp.float32)
    params = ffn.init(jax.random.PRNGKey(0), x)["params"]
    out, _ = ffn.apply({"params": params}, x)
    # C = max(1, 0.01*64/2) = 1 slot per expert -> at most 2 nonzero rows
    nonzero_rows = int(jnp.sum(jnp.any(jnp.abs(out) > 0, axis=-1)))
    assert nonzero_rows <= 2


def test_moe_sensorformer_forward():
    m = MoESensorFormer(features=18, d_model=32, num_heads=2, num_layers=2,
                        num_experts=4)
    x = jnp.asarray(_x())
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    pred, aux = m.apply({"params": params}, x)
    assert pred.shape == x.shape
    assert np.isfinite(float(aux))


def test_expert_param_specs_target_only_expert_weights():
    m = MoESensorFormer(features=6, d_model=16, num_heads=2, num_layers=1,
                        num_experts=4)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.zeros((2, 8, 6), jnp.float32))["params"]
    specs = expert_param_specs(params)
    assert specs["block0"]["moe"]["w1"] == P("expert")
    assert specs["block0"]["moe"]["router"]["kernel"] == P()
    assert specs["embed"]["kernel"] == P()


def test_ep_matches_dense_dispatch_when_no_drops():
    """With capacity >= all tokens, every token is routed; the expert-
    parallel all_to_all path must reproduce the dense einsum path exactly."""
    E = 4
    mesh = make_mesh((2, 2), ("data", "expert"), devices=jax.devices()[:4])
    # capacity_factor = E guarantees C >= N_local, so no token ever drops
    model = MoESensorFormer(features=6, d_model=16, num_heads=2, num_layers=1,
                            num_experts=E, capacity_factor=float(E))
    x = _x(B=8, T=8, F=6, seed=3)
    dense_params = model.init(jax.random.PRNGKey(0), jnp.asarray(x))["params"]
    pred_dense, _ = model.apply({"params": dense_params}, jnp.asarray(x))

    init, step, put_x = make_ep_train_step(model, optax.sgd(0.0), mesh)
    state = init(jax.random.PRNGKey(0), x)

    # run the sharded forward via the loss's mse output against the oracle
    _, metrics = step(state, put_x(x))
    want = float(jnp.mean(jnp.square(pred_dense[:, :-1] - x[:, 1:])))
    np.testing.assert_allclose(float(metrics["mse"]), want, rtol=1e-4)


def test_ep_train_step_learns():
    mesh = make_mesh((2, 4), ("data", "expert"))
    model = MoESensorFormer(features=6, d_model=16, num_heads=2, num_layers=1,
                            num_experts=8, capacity_factor=2.0)
    init, step, put_x = make_ep_train_step(model, optax.adam(1e-2), mesh)
    x = _x(B=8, T=8, F=6, seed=4)
    state = init(jax.random.PRNGKey(1), x)
    losses = []
    for _ in range(5):
        state, m = step(state, put_x(x))
        losses.append(float(m["mse"]))
    assert losses[-1] < losses[0]
    # expert weights actually sharded: local leading dim = E/ep = 8/4 = 2
    w1 = state.params["block0"]["moe"]["w1"]
    assert w1.sharding.shard_shape(w1.shape)[0] == 2


# ---------------------------------------- ops.moe: router and expert forms
def _route_softmax_by_autodiff(u, gate, top_k):
    """`ops.moe.route` under `softmax_topk` as plain differentiable
    code: the largest raw logits, a softmax over the selected."""
    s = jnp.dot(u, gate, precision=jax.lax.Precision.HIGHEST)
    picked, experts = jax.lax.top_k(s, top_k)
    return experts, jax.nn.softmax(picked, axis=-1)


def test_the_softmax_over_selected_router_and_its_backward():
    """Selection and weights as the plain form gives them — equal to a
    softmax over all the logits renormalised over the selected — and the
    hand-written backward equal to `jax.grad` of the plain form, to the
    stream and to the router's weights; no bias, and none returned."""
    import pytest

    from iotml.ops import moe

    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        u, gate, mix = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                        for shape in ((96, 32), (32, 64), (96, 6)))
        experts, weights = moe.route(u, gate, None, 6, 1.0, "softmax_topk")
        want_e, want_w = _route_softmax_by_autodiff(u, gate, 6)
        assert (experts == want_e).all()
        np.testing.assert_allclose(weights, want_w, rtol=1e-6)
        full = jax.nn.softmax(jnp.dot(
            u, gate, precision=jax.lax.Precision.HIGHEST), axis=-1)
        picked = jnp.take_along_axis(full, experts, axis=-1)
        np.testing.assert_allclose(
            weights, picked / picked.sum(axis=-1, keepdims=True), rtol=1e-5)
        got = jax.grad(lambda u, g: jnp.sum(moe.route(
            u, g, None, 6, 1.0, "softmax_topk")[1] * mix), (0, 1))(u, gate)
        want = jax.grad(lambda u, g: jnp.sum(
            _route_softmax_by_autodiff(u, g, 6)[1] * mix), (0, 1))(u, gate)
        for g, w in zip(got, want):
            assert float(jnp.abs(g - w).max()) \
                <= 2e-6 * float(jnp.abs(w).max())
    # the sigmoid form is the one it was, and an unknown form is refused
    bias = jnp.zeros((64,))
    assert all((a == b).all() for a, b in zip(
        moe.route(u, gate, bias, 6, 2.5),
        moe.route(u, gate, bias, 6, 2.5, "sigmoid")))
    with pytest.raises(ValueError, match="router form"):
        moe.route(u, gate, None, 6, 1.0, "softmax")


def test_the_relu_gated_tiles_match_the_dense_masked_experts():
    """`relu(g) ⊙ v`, two products wide: the live tiles against every
    expert held applied to every token (`experts_dense`), the output and
    every gradient; and with ONE token's assignment dropped from the
    plan the tiles differ from the dense form in that token's row alone,
    by that expert's term exactly."""
    import pytest

    from iotml.ops import moe

    assert moe.EXPERT_FORMS["relu_gated"] == 2
    rng = np.random.default_rng(5)
    N, d, f, E, held, K = 80, 32, 24, 16, 4, 3
    x, gate, w_in, w_out, mix = (
        jnp.asarray(rng.normal(size=shape), jnp.float32) * scale
        for shape, scale in (((N, d), 1.0), ((d, E), 1.0),
                             ((held, d, 2 * f), 0.2), ((held, f, d), 0.2),
                             ((N, d), 1.0)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "TILE", 16)
        jax.clear_caches()
        experts, weights = moe.route(x, gate, None, K, 1.0, "softmax_topk")

        def tiles(x, w_in, w_out, weights, drop=None):
            e = experts if drop is None else experts.at[drop].set(E - 1)
            plan = moe.dispatch_plan(e, weights, 0, held, E)
            return moe.experts_apply(x, plan, w_in, w_out, "relu_gated")

        def dense(x, w_in, w_out, weights):
            return moe.experts_dense(x, experts, weights, w_in, w_out, 0,
                                     held, "relu_gated")

        with jax.default_matmul_precision("highest"):
            got, want = (jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * mix), (0, 1, 2, 3))(
                    x, w_in, w_out, weights) for fn in (tiles, dense))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert float(jnp.abs(g - w).max()) \
                    <= 1e-5 * max(float(jnp.abs(w).max()), 1e-30)
            # the form is the one written out
            one = jnp.maximum(x @ w_in[0, :, :f], 0) * (x @ w_in[0, :, f:])
            np.testing.assert_allclose(
                moe.expert_hidden(x @ w_in[0], "relu_gated"), one, rtol=1e-6)
            # one token's assignment to an expert held, dropped
            n, k = next((n, k) for n in range(N) for k in range(K)
                        if int(experts[n, k]) < held)
            lost = dense(x, w_in, w_out, weights) \
                - tiles(x, w_in, w_out, weights, drop=(n, k))
            e = int(experts[n, k])
            term = (one[n] if e == 0 else moe.expert_hidden(
                x[n] @ w_in[e], "relu_gated")) @ w_out[e] * weights[n, k]
        # (the other rows' places in their tiles moved: rounding alone)
        size = np.abs(np.asarray(lost)).max(axis=1)
        rows = np.flatnonzero(size > 1e-3 * size.max())
        assert rows.tolist() == [n]
        np.testing.assert_allclose(lost[n], term, rtol=1e-4, atol=1e-6)
    jax.clear_caches()
