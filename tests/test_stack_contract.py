"""What every shape of stack that has a plain reference (`stacks.STACKS`,
the benchmark's seven hybrid configurations at their tiny presets on the
CPU) is held to: the program builds the reference's parameter tree, its
loss and every gradient leaf are the reference's under either attention,
and one compiled job of `Trainer.fit_compiled` is the reference's Adam
written out.  What a stack asserts beyond the common body is its row's
check here; what is a stack's own — a new part's equations, the shares
that add up, what a fit says engaged — is in its own file."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import HybridConfig, SensorHybrid
from stacks import MODES, REFERENCED, STACKS


def _model(stack, mode="dense"):
    return SensorHybrid(stacks.config(stack), attn_mode=mode)


def _built(stack):
    """The shapes of the tree the program builds for a row."""
    return stacks.shapes(jax.eval_shape(
        _model(stack).init, jax.random.PRNGKey(0),
        stacks.batch(T=STACKS[stack].window)[0])["params"])


# ----------------------------------------------------------- the tree
def _lfm2_tree(model, built):
    # `c A c c c`, the first layer with the dense MLP
    assert model.cfg.layer_types == ("short_conv", "attention") \
        + ("short_conv",) * 3
    assert model.cfg.ffn_types == ("dense_ffn",) + ("moe_ffn",) * 4
    assert sorted(built["layer0"]) == ["mixer", "mlp_in", "mlp_out",
                                       "norm1", "norm2"]
    assert sorted(built["layer1"]["mixer"]) == ["k", "k_norm", "o", "q",
                                                "q_norm", "v"]
    assert sorted(built["layer3"]["moe"]) == [
        "experts_in", "experts_out", "router", "router_bias"]
    with pytest.raises(ValueError, match="known kinds"):
        SensorHybrid(HybridConfig(layer_types=("short_conv", "conv"))).init(
            jax.random.PRNGKey(0), stacks.batch()[0])


def _ouro_tree(model, built):
    # one set of layers however many passes, four norms a block, ONE
    # final norm, head and gate
    assert (model.cfg.loop_steps, model.cfg.post_norms) == (4, True)
    assert sorted(built) == ["embed", "exit_gate", "head", "layer0",
                             "layer1", "norm_f"]
    assert built["exit_gate"] == {"kernel": (64, 1), "bias": (1,)}
    with pytest.raises(ValueError, match="at least one pass"):
        SensorHybrid(HybridConfig(loop_steps=0)).init(
            jax.random.PRNGKey(0), stacks.batch()[0])


def _smallthinker_tree(model, built):
    # `G W W W`: one mixer kind's parameters under two values of
    # `layer_types`; a router without a bias, no shared expert
    assert model.cfg.layer_types == ("attention",) \
        + ("window_attention",) * 3
    assert model.cfg.rope_layout == (0, 1, 1, 1)
    assert (model.cfg.attn_window, model.cfg.router_input,
            model.cfg.router_form, model.cfg.expert_form) \
        == (24, "block", "softmax_topk", "relu_gated")
    for i in range(4):
        assert sorted(built[f"layer{i}"]) == ["mixer", "moe", "norm1",
                                              "norm2"]
        assert sorted(built[f"layer{i}"]["mixer"]) == ["k", "o", "q", "v"]
        assert sorted(built[f"layer{i}"]["moe"]) == [
            "experts_in", "experts_out", "router"]
    for bad in (dict(layer_types=("window_attention",)),
                dict(layer_types=("attention",), rope_layout=(1, 0)),
                dict(layer_types=("attention",), router_input="mixer")):
        with pytest.raises(ValueError, match="known kinds"):
            SensorHybrid(HybridConfig(**bad)).init(
                jax.random.PRNGKey(0), stacks.batch()[0])


TREES = {"lfm2": _lfm2_tree, "ouro": _ouro_tree,
         "smallthinker": _smallthinker_tree}


@pytest.mark.parametrize("stack", REFERENCED)
def test_the_stack_builds_the_references_tree(stack):
    """The program's parameter tree is the reference's, shape by shape,
    and counts what the configuration's `benchmark/*_ops.py` counts."""
    mod, cfg = stacks.reference(stack)
    built = _built(stack)
    assert built == stacks.shapes(mod.init_params(3))
    ops = stacks.load_module("bench_" + STACKS[stack].ops, os.path.join(
        stacks.ROOT, "benchmark", STACKS[stack].ops + ".py"))
    assert ops.parameters(cfg) == stacks.parameters(built)
    TREES.get(stack, lambda *_: None)(_model(stack), built)


# ------------------------------------------- the loss and its gradient
def _routes(layers, per_token, moved):
    """Expert layers `layers`: the router's bias gets no gradient, the
    leaf `moved` of the layer does, and every assignment of every token
    was reported."""
    def check(grads, reports):
        for i in layers:
            moe = grads[f"layer{i}"]["moe"]
            assert not np.asarray(moe["router_bias"]).any()
            assert np.asarray(jax.tree.leaves(moe[moved])[0]).any()
        assert [int(c.sum()) for c in jax.tree.leaves(reports[0])] \
            == [2 * 40 * per_token] * len(layers)
    return check


def _lfm2_gradients(grads, reports):
    for i in (0, 2, 3, 4):
        assert np.asarray(grads[f"layer{i}"]["mixer"]["conv_kernel"]).any()
    _routes((1, 2, 3, 4), 3, "router")(grads, reports)


def _ouro_gradients(grads, reports):
    # every leaf of a looped stack has a gradient: the scan's carry
    assert all(np.asarray(g).any() for g in jax.tree.leaves(grads))


def _smallthinker_gradients(grads, reports):
    # no bias leaf; every router and every projection has a gradient
    for i in range(4):
        assert "router_bias" not in grads[f"layer{i}"]["moe"]
        assert np.asarray(grads[f"layer{i}"]["moe"]["router"]).any()
        assert all(np.asarray(g).any() for g in jax.tree.leaves(
            grads[f"layer{i}"]["mixer"]))
    assert [int(c.sum()) for c in jax.tree.leaves(reports[0])] \
        == [2 * 40 * 3] * 4


GRADIENTS = {"smallthinker": _smallthinker_gradients,
             "kimi": _routes((1, 2), 3, "router"),
             "nemotron": _routes((1, 3), 5, "latent_in"),
             "lfm2": _lfm2_gradients, "ouro": _ouro_gradients}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("stack", REFERENCED)
def test_model_matches_the_plain_reference(stack, mode):
    """Loss and every gradient leaf from the same seeded weights: the
    chunked scan against the stepped recurrence, the tiles against the
    dense-masked experts, the scan over the passes against Python-level
    passes — under `dense` attention and the kernels (interpreted)."""
    x = stacks.batch(T=STACKS[stack].window)[0]
    model, weights = _model(stack, mode), stacks.params(stack, 3)
    # at this size the budget takes every candidate, as `all` holds it to
    held = 5 * sum(p.size * p.dtype.itemsize
                   for p in jax.tree.leaves(weights))
    tokens = x.shape[0] * x.shape[1]
    assert sum(c.bytes for c in hybrid.budget_candidates(
        model.cfg, tokens, 4)) <= hybrid.remat_budget(
            hybrid.device_bytes(), held, sum(model._kept_bytes(x).values()),
            hybrid.backward_bytes(model.cfg, tokens, 4))
    got = stacks.policy_run(stack, mode, "all", "highest")
    want, wants = stacks.reference_gradient(stack)
    assert abs(got.loss - float(want)) <= 1e-5 * abs(float(want))
    stacks.close(got.grads, wants)
    GRADIENTS.get(stack, lambda *_: None)(got.grads, got.reports)


# ------------------------------------------------- one compiled job
def _counts(history, layers):
    reports = history["reports"]["reports"]
    counts = [np.asarray(jax.tree.leaves(reports[f"layer{i}"])[0])
              for i in layers]
    assert [c.shape for c in counts] == [(2, 2, 16)] * len(layers)
    return counts


def _first_steps_counts(layers, per_token=None):
    """The expert counts read back with the losses — every assignment
    of every token — the first step's against the reference's router."""
    def check(history, mod, weights, stacked):
        counts = _counts(history, layers)
        if per_token:
            assert all(int(c.sum()) == 2 * 2 * 2 * 40 * per_token
                       for c in counts)
        _, first = getattr(mod, "_km", mod)._loss_counts(
            weights, *(v[0] for v in stacked))
        assert np.array_equal(np.stack([c[0, 0] for c in counts]), first)
    return check


def _ouro_fit(history, mod, weights, stacked):
    # the passes' losses and exit masses, against the reference's
    said = history["reports"][hybrid.OBJECTIVE]
    assert said[hybrid.PASS_LOSS].shape == said[hybrid.EXIT_MASS].shape \
        == (2, 2, 4)
    _, (first_loss, first_mass) = mod._objective(
        weights, *(v[0] for v in stacked))
    np.testing.assert_allclose(said[hybrid.PASS_LOSS][0, 0], first_loss,
                               rtol=1e-5)
    np.testing.assert_allclose(said[hybrid.EXIT_MASS][0, 0], first_mass,
                               rtol=1e-5)


FITS = {"kimi": _first_steps_counts((1, 2), 3),
        "nemotron": _first_steps_counts((1, 3), 5),
        "lfm2": _first_steps_counts((1, 2, 3, 4)), "ouro": _ouro_fit,
        "smallthinker": _first_steps_counts((0, 1, 2, 3), 3)}


@pytest.mark.parametrize("stack", REFERENCED)
def test_two_step_fit_matches_the_reference(stack):
    """`Trainer.fit_compiled` → the scanned fit, an Adam step a batch
    and two epochs, against the reference's fit written out: losses,
    the parameters' change, both moments — and what the stack reports,
    read back with them."""
    row = STACKS[stack]
    mod, cfg = stacks.reference(stack)
    batches = [stacks.batch(T=row.window, seed=s) for s in row.fit_seeds]
    weights = mod.init_params(5)
    stacked = [jnp.stack(v) for v in zip(*batches)]
    trainer = stacks.seeded_trainer(_model(stack), weights, batches[0][0])
    rate = cfg["model"]["optimizer"]
    was, rate["learning_rate"] = rate["learning_rate"], 1e-3
    try:
        with jax.default_matmul_precision("highest"):
            history = trainer.fit_compiled(stacks.jobs(batches), epochs=2)
            p, mu, nu, losses = mod.make_fit(mod.loss_fn, 2)(
                weights, *stacked)
            assert history["fit"] == "scanned"
            np.testing.assert_allclose(history["loss"], losses, rtol=1e-5)
            adam = trainer.state.opt_state[0]
            stacks.close(
                jax.tree.map(lambda a, b: a - b, trainer.state.params,
                             weights),
                jax.tree.map(lambda a, b: a - b, p, weights),
                rtol=row.update_rtol)
            stacks.close(adam.mu, mu, rtol=row.moments_rtol)
            stacks.close(adam.nu, nu, rtol=row.moments_rtol)
            FITS.get(stack, lambda *_: None)(history, mod, weights, stacked)
    finally:
        rate["learning_rate"] = was


# ------------------------------------------------- heads that fill lanes
@pytest.mark.parametrize("mode,in_kernel", [("flash_interpret", 2),
                                            ("dense", 0)])
@pytest.mark.parametrize("stack,qk_norm", [   # the stacks that turn them
    pytest.param("lfm2", 1, id="lfm2"), pytest.param("ouro", 0, id="ouro")])
def test_a_fit_at_heads_that_fill_the_lanes_says_which_form_turned(
        stack, qk_norm, mode, in_kernel):
    """A stack whose grouped attention turns its heads, at eight heads
    of 16 on eight key/value heads and a width of 128 — `H·D = G·D` =
    one 128-lane tile: under the kernels q and k are turned by
    `iotml_rope` on `[B, T, H·D]`, after the heads' norms where it has
    them (`iotml_attn_rotary_kernel` 2), under `dense` by the pair form
    (0), and the compiled job's losses are the reference's either way."""
    from iotml.obs.metrics import default_registry

    mod, cfg = stacks.tiny(stack, f"bench_{stack}_lanes_{mode}",
                           hidden_size=128, num_attention_heads=8,
                           num_key_value_heads=8)
    cfg["model"]["optimizer"]["learning_rate"] = 1e-3
    jax.clear_caches()
    batches = [stacks.batch(seed=s) for s in (1, 2)]
    weights = mod.init_params(5)
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode=mode)
    trainer = stacks.seeded_trainer(model, weights, batches[0][0])
    with jax.default_matmul_precision("highest"):
        history = trainer.fit_compiled(stacks.jobs(batches), epochs=1)
        *_, losses = mod.make_fit(mod.loss_fn, 1)(
            weights, *(jnp.stack(v) for v in zip(*batches)))
    got = default_registry.collect()
    assert got["iotml_attn_rotary_kernel"] == in_kernel
    assert got["iotml_attn_rotary_dim"] == 16
    assert got["iotml_attn_qk_norm"] == qk_norm
    np.testing.assert_allclose(history["loss"], losses, rtol=1e-4)
