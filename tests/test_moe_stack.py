"""The latent-attention, sparse-expert stack (`models.hybrid.SensorHybrid`
with `mla` mixers and `moe_ffn` layers) against the benchmark's plain
reference (`stacks.reference`): the chip's-share cut (the shares add up
to the uncut layer), the dropless dispatch under the worst imbalance
against the dense-masked form, the selection-only bias, rotary
positions, and what a fit says of the routing.  The model and one
compiled job against the reference are the `kimi` cases of
`test_stack_contract.py`.  All at a tiny preset on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models.hybrid import SensorHybrid
from iotml.models.latent_moe import ExpertLayer
from iotml.ops import moe
from stacks import batch as _batch
from stacks import close as _close


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("kimi")


def test_the_seeded_weights_keep_the_labelling_the_file_states(ref):
    """The experts held are the contiguous range the file states, of
    seeded weights as they come: `init_params` is one seeded call, and
    nothing relabels the router's outputs or moves b afterwards."""
    mod, cfg = ref
    assert mod._held() == (0, 4, 16)
    seeded = jax.jit(lambda k: mod._init(k))(jax.random.PRNGKey(7))
    made = mod.init_params(7)
    assert jax.tree.structure(made) == jax.tree.structure(seeded)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(seeded)):
        assert np.array_equal(a, b)
    assert made["layer1"]["moe"]["router"].shape == (64, 16)
    assert made["layer1"]["moe"]["experts_in"].shape[0] == 4


# ------------------------------------------------------- the chip's share
def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen experts, three a token, one shared expert, four shares
    of four: the routed parts the four shares give, and the shared part
    counted once, are what the uncut reference layer gives."""
    whole, cfg = stacks.tiny("kimi", "bench_kimi_uncut", n_routed_experts=16)
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.normal(size=(2, 40, 64)), jnp.float32)
    p = jax.jit(lambda k: whole._init(k))(jax.random.PRNGKey(11))[
        "layer1"]["moe"]
    with jax.default_matmul_precision("highest"):
        want, counts = whole._experts_layer(p, u)
        shared = whole._gated(u, p["shared_in"]["kernel"],
                              p["shared_out"]["kernel"])
        total = jnp.zeros_like(u)
        for first in (0, 4, 8, 12):
            share = dict(p, experts_in=p["experts_in"][first:first + 4],
                         experts_out=p["experts_out"][first:first + 4])
            layer = ExpertLayer(whole.hybrid_config(dict(
                cfg, n_routed_experts=4, experts_held={"first": first})))
            out, reports = layer.apply({"params": share}, u,
                                       mutable=["reports"])
            # every share routes over all sixteen, and alike
            assert np.array_equal(
                reports["reports"]["expert_counts"], counts)
            total = total + (out - shared)
    whole.use(cfg)
    assert int(counts.sum()) == 2 * 40 * 3
    _close(total + shared, want, rtol=1e-5)


# -------------------------------------------------------------- dropless
@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 64 rows, so that a few hundred tokens are many tiles and
    an expert's group ends inside one."""
    monkeypatch.setattr(moe, "TILE", 64)


@pytest.mark.parametrize("forced,per_token,form,latent", [
    # every token takes only experts held, the worst: both forms of an
    # expert, at the stream's width and in a latent
    ("all_held", 3, "gated_silu", 0),
    ("all_held", 3, "relu2", 0),
    ("all_held", 3, "gated_silu", 8),
    ("all_held", 3, "relu2", 8),
    ("none_held", 0, "gated_silu", 0),   # no live tile at all
    ("one_expert", 1, "relu2", 8),       # every token the same expert held
    ("seeded", None, "gated_silu", 0),   # whatever the seeded router does
    ("seeded", None, "relu2", 8),
])
def test_dispatch_is_dropless_under_the_worst_imbalance(small_tiles, forced,
                                                        per_token, form,
                                                        latent):
    """No capacity and no token falls through: the tiles' result and
    every gradient against the dense-masked form (each expert held
    applied to every token, weighted by zero where it was not chosen).
    In a latent the router reads x, the experts x W_down, and the sum
    goes back through W_back: both projections' gradients are held too."""
    N, d, f, E, K, first, held = 300, 16, 8, 16, 3, 4, 4
    width = latent or d
    rng = np.random.default_rng(2)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, gate, w_in, w_out, w = arr(N, d), arr(d, E), \
        arr(held, width, moe.EXPERT_FORMS[form] * f), arr(held, f, width), \
        arr(N, d)
    down, back = arr(d, width), arr(width, d)
    here = (np.arange(E) >= first) & (np.arange(E) < first + held)
    bias = {"all_held": np.where(here, 10.0, 0.0),
            "none_held": np.where(here, -10.0, 0.0),
            "one_expert": np.where(np.arange(E) == first + 2, 10.0,
                                   np.where(here, -10.0, 0.0)),
            "seeded": np.zeros(E)}[forced]
    bias = jnp.asarray(bias, jnp.float32)

    def around(apply, x, down, back):
        """The experts at the stream's width, or in the latent."""
        return apply(x @ down) @ back if latent else apply(x)

    def tiles(x, gate, w_in, w_out, down, back):
        plan = moe.dispatch_plan(*moe.route(x, gate, bias, K, 2.5),
                                 first, held, E)
        out = around(lambda z: moe.experts_apply(z, plan, w_in, w_out, form),
                     x, down, back)
        return jnp.sum(w * out), plan

    def dense(x, gate, w_in, w_out, down, back):
        e, r = moe.route(x, gate, bias, K, 2.5)
        return jnp.sum(w * around(
            lambda z: moe.experts_dense(z, e, r, w_in, w_out, first, held,
                                        form), x, down, back))

    args = (x, gate, w_in, w_out, down, back)
    with jax.default_matmul_precision("highest"):
        (got, plan), grads = jax.jit(jax.value_and_grad(
            tiles, argnums=tuple(range(6)), has_aux=True))(*args)
        want, wants = jax.jit(jax.value_and_grad(
            dense, argnums=tuple(range(6))))(*args)
    if latent and forced != "none_held":   # the projections are in the path
        assert np.asarray(grads[4]).any() and np.asarray(grads[5]).any()
    landed = int(plan.counts[first:first + held].sum())
    if per_token is not None:
        assert landed == N * per_token
    # every assignment held has a row of a live tile, and no other has
    live = int(plan.live_tiles)
    assert live == int(
        np.ceil(np.asarray(plan.counts[first:first + held]) / 64).sum())
    assert int(plan.tile_rows[:live].sum()) == landed
    assert not np.asarray(plan.tile_rows[live:]).any()
    assert plan.tile_rows.shape[0] * 64 == moe.dispatch_rows(N, K, held)
    assert int(moe.walked_rows(plan.counts[first:first + held], N).sum()) \
        == live * 64
    rows = np.concatenate([
        np.asarray(plan.token)[f:f + r] for f, r in
        zip(np.asarray(plan.tile_first[:live]),
            np.asarray(plan.tile_rows[:live]))] + [np.zeros(0, np.int32)])
    chosen = np.asarray(moe.route(x, gate, bias, K, 2.5)[0])
    assert np.array_equal(np.sort(rows), np.sort(np.nonzero(
        (chosen >= first) & (chosen < first + held))[0]))
    assert float(abs(got - want)) <= 2e-4 * max(float(abs(want)), 1.0)
    _close(grads, wants)


def test_a_dropped_tile_shows(small_tiles):
    """The walk is the live tiles and every one of them is needed: a
    plan that says one tile fewer loses that tile's assignments from
    the result, those and no others."""
    N, d, f, E, K, first, held = 300, 16, 8, 16, 3, 4, 4
    rng = np.random.default_rng(3)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, gate, w_in, w_out = arr(N, d), arr(d, E), arr(held, d, 2 * f), \
        arr(held, f, d)
    bias = jnp.zeros((E,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        plan = moe.dispatch_plan(*moe.route(x, gate, bias, K, 2.5),
                                 first, held, E)
        whole = moe.experts_apply(x, plan, w_in, w_out)
        live = int(plan.live_tiles)
        assert live >= 2
        short = moe.experts_apply(
            x, plan._replace(live_tiles=plan.live_tiles - 1), w_in, w_out)
    at, rows = int(plan.tile_first[live - 1]), int(plan.tile_rows[live - 1])
    lost = np.zeros(N, bool)
    lost[np.asarray(plan.token)[at:at + rows]] = True
    moved = np.asarray(jnp.abs(whole - short).max(axis=1)) > 0
    assert rows > 0 and np.array_equal(moved, lost)


def _plan_by_gather(experts, weights, first, held, n_experts):
    """`ops.moe.dispatch_plan` as it stood before the weights rode the
    sort: the (key, index) pairs sorted, the flat weights gathered by
    the sorted order — autodiff's way back is that gather's transpose, a
    scatter-add of scalars.  The reference the sort form is held to."""
    N, K = experts.shape
    local = experts.reshape(-1) - first
    _, order = jax.lax.sort(
        (jnp.where((local >= 0) & (local < held), local, held),
         jnp.arange(N * K, dtype=jnp.int32)), num_keys=1, is_stable=True)
    plan = moe.dispatch_plan(experts, jax.lax.stop_gradient(weights), first,
                             held, n_experts)
    return plan._replace(weight=jnp.concatenate(
        [weights.reshape(-1)[order],
         jnp.zeros((moe._tile(N),), weights.dtype)]))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("K,first,held,idle", [
    (3, 4, 4, None),     # the shape of the tests above
    (6, 2, 3, None),     # top_k > held: a token's experts held are fewer
    (5, 0, 8, 5),        # an expert held that no token takes
    (2, 15, 1, None),    # one expert held, the last routed over
])
def test_the_routing_weights_ride_the_plans_sort(small_tiles, seed, K, first,
                                                 held, idle):
    """`Dispatch.weight` is the sort's own output and its cotangents
    come back through a sort by the order: bit for bit the gather form's
    weights and gradient (a sort moves values; a permutation's
    scatter-add adds nothing to anything), the dense-masked form's
    gradient to the file's tolerance — and no gather and no scatter-add
    of the N·K scalars in the gradient's jaxpr, where the gather form
    has one of each."""
    N, d, f, E = 300, 16, 8, 16
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, gate, w_in, w_out, w = arr(N, d), arr(d, E), arr(held, d, 2 * f), \
        arr(held, f, d), arr(N, d)
    bias = jnp.zeros((E,), jnp.float32) if idle is None \
        else jnp.where(jnp.arange(E) == idle, -10.0, 0.0)
    experts, weights = moe.route(x, gate, bias, K, 2.5)
    if idle is not None:
        assert not (experts == idle).any()

    def through(plan_of):
        def loss(weights):
            plan = plan_of(experts, weights, first, held, E)
            return jnp.sum(w * moe.experts_apply(x, plan, w_in, w_out)), plan
        return loss

    def dense(weights):
        return jnp.sum(w * moe.experts_dense(x, experts, weights, w_in,
                                             w_out, first, held))

    with jax.default_matmul_precision("highest"):
        (got, plan), grad = jax.jit(jax.value_and_grad(
            through(moe.dispatch_plan), has_aux=True))(weights)
        (want, by_gather), grad_by_gather = jax.jit(jax.value_and_grad(
            through(_plan_by_gather), has_aux=True))(weights)
        grad_dense = jax.jit(jax.grad(dense))(weights)
    assert np.array_equal(plan.weight, by_gather.weight)
    assert np.array_equal(plan.token, by_gather.token)
    assert float(got) == float(want)
    assert np.array_equal(grad, grad_by_gather)
    assert np.asarray(grad).any()
    # an assignment held elsewhere moves nothing here
    here = np.asarray((experts >= first) & (experts < first + held))
    assert not np.asarray(grad)[~here].any()
    _close(grad, grad_dense)
    # the gradient's jaxpr, counted as `test_remat_policy.py` counts it
    def moves(plan_of):
        counts = stacks.count(jax.make_jaxpr(jax.grad(
            through(plan_of), has_aux=True))(weights).jaxpr,
            stacks.what(N * K), {})
        return counts.get("gather", 0), counts.get("scatter-add", 0)

    assert moves(moe.dispatch_plan) == (0, 0)
    assert moves(_plan_by_gather) == (1, 1)


def test_the_bias_moves_the_selection_and_not_the_weights():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    none = jnp.zeros((8,), jnp.float32)
    push = none.at[5].set(10.0).at[0].set(-10.0)
    s = jax.nn.sigmoid(jnp.dot(x, gate, precision="highest"))
    for bias in (none, push):
        experts, weights = moe.route(x, gate, bias, 3, 2.446)
        picked = np.take_along_axis(np.asarray(s), np.asarray(experts), 1)
        # the weights are the selected scores WITHOUT the bias
        np.testing.assert_allclose(
            weights, 2.446 * picked / picked.sum(1, keepdims=True),
            rtol=1e-5)
        np.testing.assert_allclose(weights.sum(1), 2.446, rtol=1e-5)
    pushed = np.asarray(moe.route(x, gate, push, 3, 2.446)[0])
    assert (pushed == 5).any(1).all() and not (pushed == 0).any()
    assert not (np.asarray(moe.route(x, gate, none, 3, 2.446)[0])
                == 5).any(1).all()
    # and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(moe.route(x, gate, b, 3, 2.446)[1] ** 2))(
        push)
    assert not np.asarray(g).any()


def test_rotary_turns_neighbouring_pairs_by_position(ref):
    mod, _ = ref
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 9, 3, 8)),
                    jnp.float32)
    got = moe.rotary(x, 800000.0)
    np.testing.assert_allclose(got, mod._rotary(x), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)   # t = 0
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # scores depend on the distance alone
    q, k = moe.rotary(jnp.broadcast_to(x[:, :1], x.shape), 800000.0), \
        moe.rotary(jnp.broadcast_to(x[:, 1:2], x.shape), 800000.0)
    dots = jnp.einsum("bthd,bshd->bhts", q, k)
    np.testing.assert_allclose(dots[..., 3, 1], dots[..., 7, 5], rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time counters after a fit — the layers by kind, the
    experts held and routed over, the rows the dispatch is built for —
    and the data read back with the losses: the assignments of the job
    by where they landed, and the load of the busiest expert held."""
    from iotml.obs import metrics as obs_metrics
    from iotml.obs.metrics import default_registry

    mod, cfg = ref
    obs_metrics.attn_rotary_kernel.set(0)   # whatever a test before traced
    x = _batch()[0]
    history, before, got, _ = stacks.tiny_fit(
        SensorHybrid(mod.hybrid_config(cfg)), monkeypatch)
    assert [got[f'iotml_model_layers{{kind="{k}"}}'] for k in
            ("mla", "attention", "mamba", "dense_ffn", "moe_ffn")] \
        == [3, 0, 0, 1, 2]
    assert got["iotml_remat_blocks"] == 3
    # what the blocks' recomputation keeps, a step: two layers' selection
    # and plan (four [80, 3] arrays — the selection, the selected scores,
    # the sorted order, the sorted weights — three fields of seven tiles
    # of 80 rows, the live tiles' count, `counts`), three layers' q and k
    # [2, 40, 4, 16 + 8]
    assert moe.plan_kept_bytes(80, 3, 4, 16) == 4 * (4 * 240 + 3 * 7 + 1 + 16)
    assert [got[f'iotml_remat_kept_bytes{{kind="{k}"}}'] for k in
            ("router", "latent_qk")] \
        == [2 * moe.plan_kept_bytes(80, 3, 4, 16),
            3 * 2 * 2 * 40 * 4 * 24 * 4]
    # and under the byte budget, here in every layer that makes one: the
    # dense MLP's first product [80, 2 x 96], two shared experts' [80, 2 x 24]
    assert got['iotml_remat_kept_bytes{kind="ffn"}'] \
        == 80 * (192 + 2 * 48) * 4
    assert got['iotml_remat_kept_layers{kind="ffn"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn"}'] == 3
    # and nothing else: no kernel ran (`dense` attention)
    stacks.only_these_kinds_are_kept(got, "router", "latent_qk", "ffn")
    assert got["iotml_latent_assembled_operands"] == 1   # k, by the mixer
    assert got['iotml_moe_experts{kind="held"}'] == 4
    assert got['iotml_moe_experts{kind="routed_over"}'] == 16
    assert got["iotml_moe_top_k"] == 3
    assert got["iotml_moe_dispatch_rows"] == moe.dispatch_rows(80, 3, 4)
    # the plan's sort carries key, index and routing weights
    assert got["iotml_moe_plan_sorted_operands"] == 3
    moved = {k: got[f'iotml_moe_assignments_total{{kind="{k}"}}']
             - before.get(f'iotml_moe_assignments_total{{kind="{k}"}}', 0.0)
             for k in ("held", "elsewhere")}
    # two expert layers x 80 tokens x 3 a token x 3 steps x 2 epochs
    assert moved["held"] + moved["elsewhere"] == 2 * 80 * 3 * 3 * 2
    counts = np.sum([np.asarray(c).reshape(-1, 16).sum(0)
                     for c in jax.tree.leaves(history["reports"])], axis=0)
    assert moved["held"] == counts[:4].sum() > 0
    assert got["iotml_moe_expert_load_max_over_mean"] == pytest.approx(
        counts[:4].max() / counts[:4].mean())
    # with the kernels, three layers' out [2, 40, 4, 16] and lse [2, 4, 40]
    jax.eval_shape(SensorHybrid(mod.hybrid_config(cfg),
                                attn_mode="flash_interpret").init,
                   jax.random.PRNGKey(0), x)
    got = default_registry.collect()
    assert got['iotml_remat_kept_bytes{kind="flash"}'] \
        == 3 * (2 * 40 * 4 * 16 * 4 + 2 * 4 * 40 * 4)
    # latent attention turns a 16-wide slice of its heads by `moe.rotary`
    # under either mode, never by the call `iotml_rope`
    assert got["iotml_attn_rotary_kernel"] == 0


def test_a_model_that_reports_nothing_fits_the_program_it_had():
    """No expert layer, no report: the scanned fit returns its two
    vectors and its jaxpr holds no trace of the collection."""
    import optax

    from iotml.models.hybrid import HybridConfig
    from iotml.train.loop import TrainState, make_scanned_fit

    model = SensorHybrid(HybridConfig(layer_types=("mla", "attention")))
    assert model.report_collections == ()
    x, y, m = _batch()
    tx = optax.adam(1e-3)
    state = TrainState.create(model, jax.random.PRNGKey(0), x, tx=tx)
    _, out = make_scanned_fit(model, tx, supervised=True)(
        state, x[None], y[None], m[None], epochs=2)
    assert len(out) == 2 and out[0].shape == (2,)
