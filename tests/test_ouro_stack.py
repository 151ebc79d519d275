"""The looped stack (`models.hybrid.SensorHybrid` with `loop_steps` > 1:
one set of sandwich-normed layers run several times a step, a final norm
closing every pass, one head and one exit gate reading every pass's
output, and the expected loss under the exit distribution as the
model's own objective): each new part against the equations of the
benchmark's plain reference (`stacks.reference`), outputs and every
gradient; the loop against an unrolled stack of copies; what a fit says
of the new parts; and the accepted stacks' parameter trees, which the
new fields leave alone.  The tree, the model and one compiled job
against the reference are the `ouro` cases of `test_stack_contract.py`.
All at a tiny preset on the CPU."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import HybridBlock, HybridConfig, SensorHybrid
from iotml.train.loop import make_loss_fn
from stacks import batch as _batch
from stacks import close as _close
from stacks import stream as _stream

L, R = 2, 4   # the preset's layers, and the passes the file states


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("ouro")


def _params(mod, seed):
    """Seeded weights whose norms' weights are not all one."""
    return stacks.unsettled(mod.init_params(seed), seed)


# ------------------------------------------- the parts and their equations
@pytest.mark.parametrize("mode", ["dense", "flash_interpret"])
def test_sandwich_block_matches_the_references_equations(ref, mode):
    """`a = h + N2(Attn(N1(h)))`, `h' = a + N4(Mlp(N3(a)))`: four norms
    a block, sixteen-of-sixteen heads turned over their whole width — the
    output, and the gradient of every parameter and of the input."""
    mod, cfg = ref
    p = _params(mod, 3)["layer1"]
    assert sorted(p) == ["mixer", "mlp_in", "mlp_out", "norm1", "norm2",
                         "post_norm1", "post_norm2"]
    h = _stream(seed=1)
    w = _stream(seed=99)
    block = HybridBlock("attention", mod.hybrid_config(cfg), mode)

    def both(f):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p, h: jnp.sum(w * f(p, h)), argnums=(0, 1)))(p, h)

    got = both(lambda p, h: block.apply({"params": p}, h))
    _close(got, both(mod._block))
    for norm in ("norm1", "norm2", "post_norm1", "post_norm2"):
        assert np.asarray(got[1][0][norm]["scale"]).any()
    # the output norms are no identity: the pre-norm block is another one
    plain = HybridBlock("attention", dataclasses.replace(
        mod.hybrid_config(cfg), post_norms=False), mode)
    pre = {k: v for k, v in p.items() if not k.startswith("post_")}
    with jax.default_matmul_precision("highest"):
        assert float(jnp.abs(plain.apply({"params": pre}, h)
                             - block.apply({"params": p}, h)).max()) > 1e-2


@pytest.mark.parametrize("seed", [0, 1])
def test_the_exit_distribution_sums_to_one(seed):
    """`p_t = λ_t ∏_{j<t} (1 − λ_j)`, the last pass taking what is left:
    a distribution at every position, gates near 0 and 1 included, and
    the cumulative products of the plain form."""
    z = 12.0 * jnp.asarray(np.random.default_rng(seed).normal(
        size=(5, 3, 7)), jnp.float32)
    p = jnp.exp(hybrid.exit_log_probs(z))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    lam = np.asarray(jax.nn.sigmoid(z), np.float64)
    left, want = np.ones_like(lam[0]), []
    for t in range(4):
        want.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    # float32's sigmoid next to 1 leaves `1 − λ` a few digits
    np.testing.assert_allclose(p, np.stack(want + [left]), rtol=2e-5,
                               atol=1e-6)


@pytest.mark.parametrize("beta", [0.0, 0.1])
def test_the_objective_is_the_references(ref, beta):
    """The masked mean of `Σ_t p_t ℓ_t − β H(p)` over the valid windows'
    positions, the passes' mean losses and exit masses beside it, a
    padded window counting for nothing."""
    mod, cfg = ref
    x, y, _ = _batch(B=3)
    mask = jnp.asarray([1.0, 0.0, 1.0])
    params = _params(mod, 5)
    model = SensorHybrid(dataclasses.replace(
        mod.hybrid_config(cfg), exit_entropy_weight=beta))
    cfg["model"]["beta"] = beta
    try:
        with jax.default_matmul_precision("highest"):
            got, (pred, _, reports) = jax.jit(make_loss_fn(
                model, supervised=True))(params, x, y, mask)
            # a fresh trace: the reference reads beta as it is traced
            want, (losses, masses) = jax.jit(
                lambda *a: mod._objective(*a))(params, x, y, mask)
            last = mod.forward(params, x)
    finally:
        cfg["model"]["beta"] = 0.1
    assert float(abs(got - want)) <= 1e-5 * float(abs(want))
    said = reports[hybrid.OBJECTIVE]
    np.testing.assert_allclose(said[hybrid.PASS_LOSS], losses, rtol=1e-5)
    np.testing.assert_allclose(said[hybrid.EXIT_MASS], masses, rtol=1e-5)
    np.testing.assert_allclose(np.sum(said[hybrid.EXIT_MASS]), 1.0,
                               rtol=1e-6)
    _close(pred, last, rtol=1e-5)   # what the accuracy reads: pass R's
    if beta == 0.0:
        # no entropy term: the expectation lies among the passes' losses
        assert float(losses.min()) <= float(got) <= float(losses.max())


# --------------------------------------------- the loop and its unrolling
def test_the_loop_is_an_unrolled_stack_of_copies(ref):
    """The looped model equals `R × L` blocks, `R` closing norms, heads
    and gates, each holding a COPY of its weights, applied one after
    the other; and a shared leaf's gradient is the sum of its copies'."""
    mod, cfg = ref
    m = mod.hybrid_config(cfg)
    x, y, mask = _batch()
    params = _params(mod, 7)
    model = SensorHybrid(m)

    def unrolled(embed, copies):
        s = x @ embed["kernel"] + embed["bias"]
        preds, gates = [], []
        for c in copies:
            for i in range(L):
                s = HybridBlock("attention", m, "dense").apply(
                    {"params": c[f"layer{i}"]}, s)
            s = nn.RMSNorm(epsilon=m.eps).apply({"params": c["norm_f"]}, s)
            preds.append(s @ c["head"]["kernel"] + c["head"]["bias"])
            gates.append((s @ c["exit_gate"]["kernel"]
                          + c["exit_gate"]["bias"])[..., 0])
        return hybrid.expected_loss((jnp.stack(preds), jnp.stack(gates)),
                                    y, mask, beta=m.exit_entropy_weight)[0]

    shared = {k: v for k, v in params.items() if k != "embed"}
    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(lambda p: make_loss_fn(
            model, supervised=True)(p, x, y, mask)[0]))(params)
        want, (d_embed, d_copies) = jax.jit(jax.value_and_grad(
            unrolled, argnums=(0, 1)))(params["embed"], [shared] * R)
    assert float(abs(got - want)) <= 1e-6 * float(abs(want))
    summed = jax.tree.map(lambda *g: sum(g), *d_copies)
    _close(grads, dict(summed, embed=d_embed), rtol=2e-5)
    # the copies' gradients differ: the sum is no multiple of one
    first, last = d_copies[0]["layer0"]["mlp_out"]["kernel"], \
        d_copies[-1]["layer0"]["mlp_out"]["kernel"]
    assert float(jnp.abs(first - last).max()) \
        > 1e-2 * float(jnp.abs(first).max())


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time gauges after a fit — the passes, the norms on a
    block's outputs, what the recomputation keeps over ALL passes — the
    passes' losses and exit masses as data, the new scopes in the fit's
    program, and the fit held to ONE `device_get`."""
    from iotml.obs.metrics import default_registry

    mod, cfg = ref
    x = _batch()[0]
    model = SensorHybrid(mod.hybrid_config(cfg))
    history, _, got, gets = stacks.tiny_fit(model, monkeypatch, steps=2,
                                            epochs=1)
    assert gets == 1          # the reports came back with the losses
    assert got["iotml_model_loop_steps"] == R
    assert got["iotml_model_post_norms"] == 2
    assert [got[f'iotml_model_layers{{kind="{k}"}}'] for k in
            ("attention", "dense_ffn", "mamba", "moe_ffn")] == [L, L, 0, 0]
    assert got["iotml_remat_blocks"] == L      # the layers, not R x L
    assert got["iotml_attn_rotary_dim"] == 16
    # `dense` attention (and four heads of 16 fill no 128-lane tile):
    # XLA's pair form turned them
    assert got["iotml_attn_rotary_kernel"] == 0
    assert got["iotml_attn_qk_norm"] == 0
    # over all passes: every block's input and a pass's closing's; the
    # MLP's first product [80, 2 x 96] in both layers' four
    # applications; no kernel under `dense`, so no `flash` name
    assert got['iotml_remat_kept_bytes{kind="loop_inputs"}'] \
        == R * (L + 1) * 80 * 64 * 4
    assert got['iotml_remat_kept_bytes{kind="ffn"}'] == R * L * 80 * 192 * 4
    assert got['iotml_remat_kept_layers{kind="ffn"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn"}'] == L
    # a sandwich block: the budget buys the feed-forward part's output
    # ahead of its post norm FIRST (a product over 96 against the first
    # product's 64), [80, 64] an application — and at this size all fits
    assert got['iotml_remat_kept_bytes{kind="ffn_out"}'] \
        == R * L * 80 * 64 * 4
    assert got['iotml_remat_kept_layers{kind="ffn_out"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn_out"}'] == L
    # the mixer's output ahead of ITS post norm, after the first products
    assert got['iotml_remat_kept_bytes{kind="mixer_out"}'] \
        == R * L * 80 * 64 * 4
    assert got['iotml_remat_kept_layers{kind="mixer_out"}'] \
        == got['iotml_remat_keepable_layers{kind="mixer_out"}'] == L
    # and nothing else: no kernel under `dense`, so no `flash` name
    stacks.only_these_kinds_are_kept(got, "loop_inputs", "ffn", "ffn_out",
                                     "mixer_out")
    # DATA: the last fit's means, a value a pass
    said = history["reports"][hybrid.OBJECTIVE]
    for name, key in (("iotml_loop_pass_loss", hybrid.PASS_LOSS),
                      ("iotml_loop_exit_mass", hybrid.EXIT_MASS)):
        values = [got[f'{name}{{kind="pass{t + 1}"}}'] for t in range(R)]
        np.testing.assert_allclose(
            values, np.asarray(said[key]).reshape(-1, R).mean(axis=0),
            rtol=1e-6)
    assert sum(got[f'iotml_loop_exit_mass{{kind="pass{t + 1}"}}']
               for t in range(R)) == pytest.approx(1.0, rel=1e-5)
    stacks.scopes_in_the_program(
        model, mod.init_params(1), x,
        ("attn", "rope", "mlp", "post_norm", "exit_gate"))
    # with the kernels (traced, not run): out [80, 4 x 16] and lse
    # [80, 4] an application
    jax.eval_shape(SensorHybrid(mod.hybrid_config(cfg),
                                attn_mode="flash_interpret").init,
                   jax.random.PRNGKey(0), x)
    assert default_registry.collect()[
        'iotml_remat_kept_bytes{kind="flash"}'] \
        == R * L * 80 * 4 * (16 * 4 + 4)
    # a stack that is no loop says so, and has none of the new parts
    jax.clear_caches()
    plain = SensorHybrid(HybridConfig())
    made = plain.init(jax.random.PRNGKey(0), x)["params"]
    got = default_registry.collect()
    assert got["iotml_model_loop_steps"] == 1
    assert got["iotml_model_post_norms"] == 0
    # a stack without positions turns nothing, by either form
    assert got["iotml_attn_rotary_dim"] == got["iotml_attn_rotary_kernel"] \
        == 0
    assert got['iotml_remat_kept_bytes{kind="loop_inputs"}'] == 0
    assert plain.objective is None and plain.report_collections == ()
    assert "exit_gate" not in made and not any(
        k.startswith("post_norm") for k in made["layer0"])


# ------------------------------------------ the accepted stacks stand
@pytest.mark.parametrize("name", [
    "sensorformer-granite-4.0-h-micro",
    "sensorformer-kimi-vl-a3b-instruct",
    "sensorformer-nemotron-3-super-120b-a12b",
    "sensorformer-lfm2-24b-a2b",
])
def test_the_accepted_hybrid_stacks_trees_are_what_they_were(name):
    """Path by path and shape by shape, at the published widths: with
    the new fields at their defaults every accepted stack builds the
    tree its own plain reference writes down (each independent of the
    program) — one pass, no norm on a part's output, no gate, no
    objective of its own."""
    mod, cfg = stacks.load(name.removeprefix("sensorformer-"),
                           "bench_tree_for_ouro_" + name.split("-")[1])
    mod.use(cfg)
    model = SensorHybrid(mod.hybrid_config(cfg))
    assert (model.cfg.loop_steps, model.cfg.post_norms) == (1, False)
    assert model.objective is None
    tree = jax.tree.map(jnp.shape, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16, 18)))["params"])
    want = jax.tree.map(jnp.shape, jax.eval_shape(
        mod._init, jax.random.PRNGKey(0)))
    assert tree == want
    paths = {"/".join(k.key for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda s: isinstance(s, tuple))[0]}
    assert not [p for p in paths if "post_norm" in p or "exit_gate" in p]


def test_the_sequence_models_tree_is_untouched():
    """`models/transformer.py`'s stack, the benchmark's first
    configuration: its reference's tree, and a loss that is the masked
    mean squared error of one output — no objective, no reports."""
    from iotml.models.transformer import SensorFormer

    mod, cfg = stacks.load("gpt2-medium", "bench_tree_for_ouro_gpt2")
    mod.use(cfg)
    m = cfg["model"]
    model = SensorFormer(features=m["features"], d_model=m["d_model"],
                         num_heads=m["num_heads"],
                         num_layers=m["num_layers"], max_len=m["max_len"])
    tree = jax.tree.map(jnp.shape, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16, 18)))["params"])
    want = jax.tree.map(jnp.shape, jax.eval_shape(
        lambda k: mod._init(k, m["features"], m["d_model"], m["num_heads"],
                            m["num_layers"], m["mlp_ratio"], m["max_len"]),
        jax.random.PRNGKey(0)))
    assert tree == want
    assert getattr(model, "objective", None) is None
    assert not getattr(model, "report_collections", ())
