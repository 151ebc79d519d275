"""The looped stack (`models.hybrid.SensorHybrid` with `loop_steps` > 1:
one set of sandwich-normed layers run several times a step, a final norm
closing every pass, one head and one exit gate reading every pass's
output, and the expected loss under the exit distribution as the
model's own objective): each new part against the equations of the
benchmark's plain reference (loaded by path, as `benchmark/tests` loads
it), outputs and every gradient; the loop against an unrolled stack of
copies; the model and one compiled job against the reference; what a fit
says of the new parts; and the accepted stacks' parameter trees, which
the new fields leave alone.  All at a tiny preset on the CPU."""

import dataclasses
import importlib.util
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.models import hybrid
from iotml.models.hybrid import HybridBlock, HybridConfig, SensorHybrid
from iotml.train.loop import make_loss_fn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
#: width 64; 4 heads of 16 on 4 key/value heads; an MLP of 96; two
#: layers, run the file's four times
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            head_dim=16, intermediate_size=96, num_hidden_layers=2)
L, R = 2, 4


def _load(name, stem):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CONFIGS, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(CONFIGS, stem + ".json")) as fh:
        return mod, json.load(fh)


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    mod, cfg = _load("bench_ouro_reference", "sensorformer-ouro-2.6b")
    cfg.update(TINY)
    cfg["layer_types"] = cfg["layer_types"][:L]
    cfg["job"] = dict(cfg["job"], window=40)
    mod.use(cfg)
    return mod, cfg


def _batch(B=2, T=40, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, T, 18)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, 1, 18)), jnp.float32),
            jnp.ones((B,), jnp.float32))


def _stream(B=2, T=40, d=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(B, T, d)),
                       jnp.float32)


def _close(got, want, rtol=2e-4):
    """Within `rtol` of the reference's largest entry, leaf by leaf."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(jnp.abs(w).max()), 1e-30)
        assert float(jnp.abs(g - w).max()) <= rtol * scale


def _params(mod, seed):
    """Seeded weights whose norms' weights are not all one, the gate's
    bias not zero: a norm on the wrong operand, or a bias left out,
    would not hide."""
    rng = np.random.default_rng(seed)

    def unsettle(path, leaf):
        names = [k.key for k in path]
        if names[-1] == "scale" or names[-2:] == ["exit_gate", "bias"]:
            return leaf + jnp.asarray(rng.uniform(-0.5, 0.5, leaf.shape),
                                      jnp.float32)
        return leaf

    return jax.tree_util.tree_map_with_path(unsettle, mod.init_params(seed))


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


# --------------------------------------------- the model and the reference
def test_the_stack_builds_the_references_tree(ref):
    """One set of layers however many passes, four norms a block, ONE
    final norm, head and gate: the program's parameter tree is the
    reference's, shape by shape, and counts what `loop_ops.parameters`
    counts."""
    mod, cfg = ref
    model = SensorHybrid(mod.hybrid_config(cfg))
    assert (model.cfg.loop_steps, model.cfg.post_norms) == (R, True)
    shapes = jax.tree.map(jnp.shape, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _batch()[0])["params"])
    assert shapes == jax.tree.map(jnp.shape, mod.init_params(3))
    assert sorted(shapes) == ["embed", "exit_gate", "head", "layer0",
                              "layer1", "norm_f"]
    assert shapes["exit_gate"] == {"kernel": (64, 1), "bias": (1,)}
    spec = importlib.util.spec_from_file_location(
        "bench_loop_ops", os.path.join(ROOT, "benchmark", "loop_ops.py"))
    ops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ops)
    assert ops.parameters(cfg) == sum(
        int(np.prod(s)) for s in jax.tree.leaves(
            shapes, is_leaf=lambda s: isinstance(s, tuple)))
    with pytest.raises(ValueError, match="at least one pass"):
        SensorHybrid(HybridConfig(loop_steps=0)).init(
            jax.random.PRNGKey(0), _batch()[0])


@pytest.mark.parametrize("mode", ["dense", "flash_interpret"])
def test_model_matches_the_plain_reference(ref, mode):
    """Loss and every gradient leaf from the same seeded weights: the
    scan over the passes against four Python-level passes."""
    mod, cfg = ref
    x, y, mask = _batch()
    params = _params(mod, 3)
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode=mode)
    loss = make_loss_fn(model, supervised=True)
    with jax.default_matmul_precision("highest"):
        (got, _), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params, x, y, mask)
        want, wants = jax.jit(jax.value_and_grad(mod.loss_fn))(
            params, x, y, mask)
    assert float(abs(got - want)) <= 1e-5 * float(abs(want))
    _close(grads, wants)
    assert all(np.asarray(g).any() for g in jax.tree.leaves(grads))


def test_two_step_fit_matches_the_reference(ref):
    """`Trainer.fit_compiled` → the scanned fit, two Adam steps an
    epoch, against the reference's fit written out: losses, updated
    parameters, both moments — and the passes' losses and exit masses
    read back with them against the reference's."""
    from iotml.data.dataset import Batch
    from iotml.train.loop import Trainer

    mod, cfg = ref
    batches = [_batch(seed=s) for s in (1, 2)]
    params = mod.init_params(5)
    trainer = Trainer(SensorHybrid(mod.hybrid_config(cfg)), supervised=True,
                      learning_rate=1e-3)
    cfg["model"]["optimizer"]["learning_rate"] = 1e-3
    stacked = [jnp.stack(v) for v in zip(*batches)]
    try:
        trainer._ensure_state(batches[0][0])
        trainer.state = trainer.state.replace(
            params=jax.tree.map(jnp.array, params))
        with jax.default_matmul_precision("highest"):
            history = trainer.fit_compiled(
                [Batch(x=np.asarray(x), y=np.asarray(y), n_valid=2,
                       first_index=0) for x, y, _ in batches], epochs=2)
            p, mu, nu, losses = mod.make_fit(mod.loss_fn, 2)(params, *stacked)
            _, (first_loss, first_mass) = mod._objective(
                params, *(v[0] for v in stacked))
    finally:
        cfg["model"]["optimizer"]["learning_rate"] = 1e-5
    np.testing.assert_allclose(history["loss"], losses, rtol=1e-5)
    adam = trainer.state.opt_state[0]
    _close(jax.tree.map(lambda a, b: a - b, trainer.state.params, params),
           jax.tree.map(lambda a, b: a - b, p, params), rtol=2e-3)
    _close(adam.mu, mu)
    _close(adam.nu, nu)
    said = history["reports"][hybrid.OBJECTIVE]
    assert said[hybrid.PASS_LOSS].shape == said[hybrid.EXIT_MASS].shape \
        == (2, 2, R)
    np.testing.assert_allclose(said[hybrid.PASS_LOSS][0, 0], first_loss,
                               rtol=1e-5)
    np.testing.assert_allclose(said[hybrid.EXIT_MASS][0, 0], first_mass,
                               rtol=1e-5)


@pytest.mark.parametrize("mode,in_kernel", [("flash_interpret", 2),
                                            ("dense", 0)])
def test_a_fit_at_heads_that_fill_the_lanes_says_which_form_turned(
        mode, in_kernel):
    """Eight heads of 16 at a width of 128 — `H·D` = one 128-lane tile:
    under the kernels every application's q and k are turned by
    `iotml_rope` on `[B, T, H·D]` (`iotml_attn_rotary_kernel` 2), under
    `dense` by the pair form (0), and the compiled job's losses are the
    reference's either way."""
    from iotml.data.dataset import Batch
    from iotml.obs.metrics import default_registry
    from iotml.train.loop import Trainer

    mod, cfg = _load("bench_ouro_lanes_" + mode, "sensorformer-ouro-2.6b")
    cfg.update(TINY, hidden_size=128, num_attention_heads=8,
               num_key_value_heads=8)
    cfg["layer_types"] = cfg["layer_types"][:L]
    cfg["job"] = dict(cfg["job"], window=40)
    cfg["model"]["optimizer"]["learning_rate"] = 1e-3
    mod.use(cfg)
    jax.clear_caches()
    batches = [_batch(seed=s) for s in (1, 2)]
    params = mod.init_params(5)
    trainer = Trainer(SensorHybrid(mod.hybrid_config(cfg), attn_mode=mode),
                      supervised=True, learning_rate=1e-3)
    trainer._ensure_state(batches[0][0])
    trainer.state = trainer.state.replace(
        params=jax.tree.map(jnp.array, params))
    with jax.default_matmul_precision("highest"):
        history = trainer.fit_compiled(
            [Batch(x=np.asarray(x), y=np.asarray(y), n_valid=2,
                   first_index=0) for x, y, _ in batches], epochs=1)
        *_, losses = mod.make_fit(mod.loss_fn, 1)(
            params, *(jnp.stack(v) for v in zip(*batches)))
    got = default_registry.collect()
    assert got["iotml_attn_rotary_kernel"] == in_kernel
    assert got["iotml_attn_rotary_dim"] == 16
    np.testing.assert_allclose(history["loss"], losses, rtol=1e-4)


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time gauges after a fit — the passes, the norms on a
    block's outputs, what the recomputation keeps over ALL passes — the
    passes' losses and exit masses as data, the new scopes in the fit's
    program, and the fit held to ONE `device_get`."""
    from iotml.data.dataset import Batch
    from iotml.obs.metrics import default_registry
    from iotml.train import loop
    from iotml.train.loop import Trainer

    mod, cfg = ref
    jax.clear_caches()
    gets = []
    device_get = jax.device_get
    monkeypatch.setattr(loop.jax, "device_get",
                        lambda t: gets.append(1) or device_get(t))
    x, y, _ = _batch()
    model = SensorHybrid(mod.hybrid_config(cfg))
    trainer = Trainer(model, supervised=True, learning_rate=1e-5)
    history = trainer.fit_compiled(
        [Batch(x=np.asarray(x), y=np.asarray(y), n_valid=2,
               first_index=0)] * 2, epochs=1)
    got = default_registry.collect()
    assert history["fit"] == "scanned" and np.isfinite(history["loss"]).all()
    assert len(gets) == 1          # the reports came back with the losses
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
    assert got['iotml_remat_kept_bytes{kind="flash"}'] == 0
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
    # the scopes ride the program's operations
    text = jax.jit(lambda p: model.apply({"params": p}, x)).lower(
        mod.init_params(1)).as_text(debug_info=True)
    for scope in ("attn", "rope", "mlp", "post_norm", "exit_gate"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
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
    mod, cfg = _load("bench_tree_for_ouro_" + name.split("-")[1], name)
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

    mod, cfg = _load("bench_tree_for_ouro_gpt2", "sensorformer-gpt2-medium")
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
