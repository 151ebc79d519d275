"""MoE SensorFormer + expert parallelism: the expert-sharded all_to_all
path must match the single-device dense dispatch, and routing must respect
capacity with static shapes throughout."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

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


# ------------------------------ ops.moe: how a tile's rows are added back
def _tile_of(N, tile, live, rng):
    """A tile's tokens as `_tile_operands` gives them: `live` distinct
    ascending tokens, then the padding's token N."""
    tokens = np.full(tile, N, np.int32)
    tokens[:live] = np.sort(rng.choice(N, size=live, replace=False))
    return jnp.asarray(tokens)


@pytest.mark.parametrize("N,tile,d,live,zeros,form", [
    (1024, 512, 2560, 512, False, "kernel"),    # `st-train-backlog`'s rows
    (640, 512, 1024, 300, False, "kernel"),     # `ns`'s latent
    (600, 512, 256, 0, False, "kernel"),        # a tile of padding alone
    (600, 512, 256, 1, False, "kernel"),
    (600, 512, 256, 511, False, "kernel"),      # one row of padding
    (600, 512, 128, 512, True, "kernel"),       # a loop's first tile
    (200, 200, 128, 77, False, "kernel"),       # a short window's tile
    (300, 304, 96, 200, False, "scatter"),      # rows of no whole lane tile
])
def test_add_rows_is_the_scatter_add_bit_for_bit(monkeypatch, N, tile, d,
                                                 live, zeros, form):
    """`iotml_add_rows`, interpreted, against `acc.at[tokens].add(rows,
    mode="drop")`: the same float32 add of the same rows, so equal
    exactly — the live rows alone, whatever the accumulator held.  (A
    few hundred tokens: the accumulators here are of the size the rule
    leaves to XLA, so the size's threshold is taken away.)"""
    from iotml.ops import add_rows, moe

    assert moe._tile(N) == tile
    assert moe.add_rows_form(N, d, jnp.float32, "flash") == "scatter"
    monkeypatch.setattr(add_rows, "RESIDENT_BYTES", 0)
    got_form = moe.add_rows_form(N, d, jnp.float32, "flash_interpret")
    assert got_form == {"kernel": "kernel_interpret"}.get(form, form)
    assert moe.add_rows_form(N, d, jnp.float32, "dense") == "scatter"
    assert moe.add_rows_form(N, d, jnp.bfloat16, "flash") == "scatter"
    rng = np.random.default_rng(N + live)
    acc = jnp.zeros((N, d), jnp.float32) if zeros else jnp.asarray(
        rng.normal(size=(N, d)), jnp.float32)
    tokens = _tile_of(N, tile, live, rng)
    rows = jnp.asarray(rng.normal(size=(tile, d)), jnp.float32)
    want = acc.at[tokens].add(rows, mode="drop")
    shaped = acc if got_form == "scatter" else acc.reshape(N, 1, d)
    got = jax.jit(functools.partial(moe._add_rows, add=got_form))(
        shaped, tokens, jnp.int32(live), rows)
    np.testing.assert_array_equal(np.asarray(got).reshape(N, d), want)
    if 0 < live < tile:    # nothing but the live rows' tokens moved
        touched = np.flatnonzero((np.asarray(got).reshape(N, d)
                                  != np.asarray(acc)).any(axis=1))
        assert set(touched) <= set(np.asarray(tokens[:live]).tolist())


@pytest.mark.parametrize("form", ["gated_silu", "relu2", "relu_gated"])
def test_the_tiles_under_the_kernel_match_the_dense_masked_experts(form):
    """`experts_apply` with its rows added back by `iotml_add_rows`
    (interpreted: 128-wide float32 rows under `flash_interpret`): value
    and the four gradients against `experts_dense`, and against the
    scatter form's own, which they equal to rounding's last bit."""
    from iotml.ops import add_rows, moe

    rng = np.random.default_rng(7)
    N, d, f, E, held, K = 80, 128, 24, 16, 4, 3
    x, gate, w_in, w_out, mix = (
        jnp.asarray(rng.normal(size=shape), jnp.float32) * scale
        for shape, scale in (((N, d), 1.0), ((d, E), 1.0),
                             ((held, d, moe.EXPERT_FORMS[form] * f), 0.1),
                             ((held, f, d), 0.1), ((N, d), 1.0)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "TILE", 16)
        patch.setattr(add_rows, "RESIDENT_BYTES", 0)   # 80 tokens
        jax.clear_caches()
        assert moe.add_rows_form(N, d, x.dtype, "flash_interpret") \
            == "kernel_interpret"
        experts, weights = moe.route(x, gate, None, K, 1.0, "softmax_topk")

        def tiles(attn_mode, x, w_in, w_out, weights):
            plan = moe.dispatch_plan(experts, weights, 0, held, E)
            return moe.experts_apply(x, plan, w_in, w_out, form, attn_mode)

        def dense(x, w_in, w_out, weights):
            return moe.experts_dense(x, experts, weights, w_in, w_out, 0,
                                     held, form)

        with jax.default_matmul_precision("highest"):
            got, scattered, want = (jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * mix), (0, 1, 2, 3))(
                    x, w_in, w_out, weights) for fn in (
                        functools.partial(tiles, "flash_interpret"),
                        functools.partial(tiles, "dense"), dense))
        assert len(jax.tree.leaves(got)) == 5
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.abs(g - w).max()) \
                <= 1e-5 * max(float(jnp.abs(w).max()), 1e-30)
        for g, s in zip(jax.tree.leaves(got), jax.tree.leaves(scattered)):
            np.testing.assert_array_equal(g, s)
    jax.clear_caches()


@pytest.mark.parametrize("attn_mode,shape,latent,kernel,chunk", [
    ("flash", (2, 16384, 2560), 0, 2, 64),    # `st-train-backlog`: 320 MiB
    ("flash", (2, 8192, 2048), 0, 2, 128),    # `lf-train-backlog`: 128 MiB
    ("flash", (1, 8192, 2048), 0, 0, 0),      # `km`: 64 MiB, XLA's in VMEM
    ("flash", (1, 8192, 4096), 1024, 0, 0),   # `ns`'s latent: 32 MiB
    ("dense", (2, 16384, 2560), 0, 0, 0),     # the plain path
    ("flash", (2, 16384, 96), 0, 0, 0),       # a tiny preset's narrow rows
])
def test_the_gauge_says_how_a_traced_layer_adds_its_rows_back(
        attn_mode, shape, latent, kernel, chunk):
    """`iotml_moe_add_rows{kind}` after a trace of an expert layer at a
    cell's tokens and widths: the kernel where the model runs its
    kernels and the accumulator is larger than XLA keeps in VMEM,
    XLA's scatter-add for the small accumulators, under `dense` and for
    rows of no whole lane tile."""
    from iotml.models.hybrid import HybridConfig
    from iotml.models.latent_moe import ExpertLayer
    from iotml.obs.metrics import default_registry

    cfg = HybridConfig(d_model=shape[2], moe_latent=latent, experts=8,
                       experts_held=(0, 4), top_k=2, expert_dim=128)
    layer = ExpertLayer(cfg, attn_mode)
    jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                   jax.ShapeDtypeStruct(shape, jnp.float32))
    got = default_registry.collect()
    assert got['iotml_moe_add_rows{kind="kernel"}'] == kernel
    assert got['iotml_moe_add_rows{kind="scatter"}'] == 2 - kernel
    assert got["iotml_moe_add_rows_chunk"] == chunk


def test_a_start_traces_the_add_rows_kernel_once_a_shape(monkeypatch):
    """The guard of `setup_s`: a start — the state's `init`, then the
    scanned fit — of three expert layers of one shape, each block under
    the stack's recomputation: twelve call sites of `add_rows.add_rows`
    (`init`'s forward; the fit's forward, its recomputed forward and its
    backward), and the kernel's body is traced ONCE by `init` and ONCE
    by the fit, because the call is a `jax.jit` function whose cache's
    key is the operands' shapes (the fit's plain forward finds `init`'s
    trace; what autodiff traces is keyed apart, by JAX's trace context,
    once for all layers).  Another count of tokens is another
    accumulator and its own traces.  (A bare `pl.pallas_call` there was
    traced at every site: twelve times in `st-train-backlog`'s fit,
    3.65 s of its set-up.)"""
    from iotml.models.hybrid import HybridConfig, SensorHybrid
    from iotml.obs.metrics import default_registry
    from iotml.ops import add_rows
    from iotml.train.loop import TrainState, make_scanned_fit

    traced = []
    body = add_rows._step

    def counted(*refs, **geometry):
        traced.append(refs[3].shape)     # the accumulator's
        return body(*refs, **geometry)

    monkeypatch.setattr(add_rows, "_step", counted)
    monkeypatch.setattr(add_rows, "RESIDENT_BYTES", 0)    # 32 tokens
    model = SensorHybrid(HybridConfig(
        d_model=128, layer_types=("mamba",) * 3, ffn_types=("moe_ffn",) * 3,
        experts=8, experts_held=(0, 4), top_k=2, expert_dim=16,
        shared_dim=16), features=6, attn_mode="flash_interpret")
    tx = optax.adam(1e-3)

    def fresh(rng, x):
        params = model.init(rng, x)["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), apply_fn=model.apply,
                          tx=tx)

    def start(B, T=16, F=6, steps=2):
        """→ (what `init` traced, what the fit traced after it)"""
        jax.clear_caches()
        traced.clear()
        state = jax.eval_shape(fresh, jax.random.PRNGKey(0),
                               jnp.zeros((B, T, F)))
        by_init = list(traced)
        make_scanned_fit(model, tx, supervised=True).trace(
            state, *(jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
                (steps, B, T, F), (steps, B, 1, F), (steps, B))), epochs=2)
        return by_init, traced[len(by_init):]

    assert start(B=2) == ([(32, 1, 128)], [(32, 1, 128)])
    said = default_registry.collect()
    assert said['iotml_moe_add_rows{kind="kernel"}'] == 2
    assert said['iotml_model_layers{kind="moe_ffn"}'] \
        == said["iotml_remat_blocks"] == 3
    assert start(B=3) == ([(48, 1, 128)], [(48, 1, 128)])
    jax.clear_caches()
