"""The gated-short-convolution stack (`models.hybrid.SensorHybrid` with
`short_conv` mixers, grouped attention that norms and turns its queries
and keys, and expert layers without a shared expert): each new part
against the equations of the benchmark's plain reference (loaded by
path, as `benchmark/tests` loads it), outputs and every gradient; the
model and one compiled job against it; the chip's-share cut of the
expert layer (the eight shares add up to the uncut layer: there is no
part every chip computes alike); and what a fit says of the new parts.
All at a tiny preset on the CPU."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.models import hybrid
from iotml.models.hybrid import HybridConfig, SensorHybrid
from iotml.models.latent_moe import ExpertLayer
from iotml.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "sensorformer-lfm2-24b-a2b")
#: width 64; 4 query heads of 16 over 2 key/value heads; a dense MLP of
#: 96; 16 experts of 24, 3 a token, 4 held; the file's five layers,
#: `c A c c c`, the first with the dense MLP
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, moe_intermediate_size=24, num_experts=4,
            num_experts_per_tok=3)


def _reference(name, routed=16, **sizes):
    spec = importlib.util.spec_from_file_location(name, CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(CONFIG + ".json") as fh:
        cfg = json.load(fh)
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], num_experts=routed)
    cfg["job"] = dict(cfg["job"], window=40)
    cfg.update(sizes)
    mod.use(cfg)
    return mod, cfg


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return _reference("bench_lfm2_reference")


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


def _value_and_grads(f, p, u):
    """A weighted sum of f(p, u) and its gradients in p and u."""
    w = _stream(*u.shape, seed=99)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, u: jnp.sum(w * f(p, u)), argnums=(0, 1)))(p, u)


def _layer_params(mod, seed, layer):
    return jax.jit(lambda k: mod._init(k))(jax.random.PRNGKey(seed))[layer]


# ------------------------------------------- the parts and their equations
@pytest.mark.parametrize("T", [40, 130])   # 130: a lane tile and a tail
def test_short_conv_mixer_matches_the_references_equations(ref, T):
    """`[b, c, x] = u W_in`, three taps over `b ⊙ x` with nothing before
    the window, no bias, no activation, `(c ⊙ y) W_out`: the kernels
    (interpreted) on the transposed stream against three shifted
    products summed — the output, and the gradient of every parameter
    and of the input."""
    mod, cfg = ref
    p = _layer_params(mod, 3, "layer0")["mixer"]
    assert sorted(p) == ["conv_kernel", "in_proj", "out_proj"]
    assert p["conv_kernel"].shape == (3, 64)
    u = _stream(T=T, seed=T)
    mixer = hybrid.ShortConvMixer(mod.hybrid_config(cfg))
    got = _value_and_grads(lambda p, u: mixer.apply({"params": p}, u), p, u)
    want = _value_and_grads(mod._short_conv, p, u)
    _close(got, want, rtol=2e-5)
    with jax.default_matmul_precision("highest"):
        _close(mixer.apply({"params": p}, u), mod._short_conv(p, u),
               rtol=1e-5)
        # causal, and two positions deep: an input moves its own output
        # and the two after it through the taps, none before
        moved = mixer.apply({"params": p}, u.at[:, 20].add(1.0)) \
            - mixer.apply({"params": p}, u)
    reach = np.flatnonzero(np.abs(np.asarray(moved)).max(axis=(0, 2)) > 1e-7)
    assert reach.tolist() == [20, 21, 22]


@pytest.mark.parametrize("mode", ["dense", "flash_interpret"])
def test_normed_rotary_attention_matches_the_references_equations(ref, mode):
    """Queries and keys normed a head (one weight vector for the query
    heads, one for the key heads), turned over the whole head, four
    heads on two key/value heads: the output and every gradient, the
    norms' weights among them, through the program's `rotary` and
    either attention against the reference's own."""
    mod, cfg = ref
    p = _layer_params(mod, 4, "layer1")["mixer"]
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (16,)
    # unit weights would hide a norm applied to the wrong operand
    rng = np.random.default_rng(4)
    p = dict(p, q_norm={"scale": jnp.asarray(
        rng.uniform(0.5, 1.5, 16), jnp.float32)}, k_norm={"scale": jnp.asarray(
            rng.uniform(0.5, 1.5, 16), jnp.float32)})
    u = _stream(seed=5)
    attn = hybrid.GroupedAttention(mod.hybrid_config(cfg), mode)
    got = _value_and_grads(lambda p, u: attn.apply({"params": p}, u), p, u)
    want = _value_and_grads(mod._attention, p, u)
    _close(got, want)
    assert np.asarray(got[1][0]["q_norm"]["scale"]).any() \
        and np.asarray(got[1][0]["k_norm"]["scale"]).any()
    # positions matter: the same layer without the turn is another one
    plain = hybrid.GroupedAttention(dataclasses.replace(
        mod.hybrid_config(cfg), attn_rope_theta=0.0), mode)
    with jax.default_matmul_precision("highest"):
        assert float(jnp.abs(plain.apply({"params": p}, u)
                             - attn.apply({"params": p}, u)).max()) > 1e-3


def test_expert_layer_without_a_shared_expert_matches_the_reference(
        ref, monkeypatch):
    """`shared_dim` 0: no `shared_in`, no `shared_out`, the layer is the
    routed sum of the experts held — the tiles against the reference's
    dense-masked experts, the output, every gradient and the counts."""
    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    p = _layer_params(mod, 6, "layer2")["moe"]
    assert sorted(p) == ["experts_in", "experts_out", "router", "router_bias"]
    u = _stream(seed=7)
    layer = ExpertLayer(mod.hybrid_config(cfg))
    made = jax.eval_shape(layer.init, jax.random.PRNGKey(0), u)["params"]
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, p)
    got = _value_and_grads(
        lambda p, u: layer.apply({"params": p}, u, mutable=["reports"])[0],
        p, u)
    want = _value_and_grads(lambda p, u: mod._experts_layer(p, u)[0], p, u)
    _close(got, want)
    assert not np.asarray(got[1][0]["router_bias"]).any()
    with jax.default_matmul_precision("highest"):
        _, reports = layer.apply({"params": p}, u, mutable=["reports"])
        counts = mod._experts_layer(p, u)[1]
    assert np.array_equal(reports["reports"]["expert_counts"], counts)
    assert int(counts.sum()) == 2 * 40 * 3
    # the accepted layers keep theirs
    with_shared = jax.eval_shape(
        ExpertLayer(HybridConfig()).init, jax.random.PRNGKey(0), u)["params"]
    assert {"shared_in", "shared_out"} <= set(with_shared)


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """The expert layer at a small size — 16 experts, 3 a token — and its
    cut into eight shares of two: every share routes over all sixteen,
    and alike; each computes its own experts' terms; with no shared
    expert there is no part every chip computes alike, so the eight
    routed sums, nothing counted once, add up to the uncut reference's
    layer (the reference's own functions, handed all sixteen)."""
    whole, cfg = _reference("bench_lfm2_uncut", num_experts=16)
    u = _stream(seed=11)
    p = _layer_params(whole, 11, "layer2")["moe"]
    assert p["experts_in"].shape[0] == 16
    with jax.default_matmul_precision("highest"):
        want, counts = whole._experts_layer(p, u)
        total = jnp.zeros_like(u)
        for first in range(0, 16, 2):
            share = dict(p, experts_in=p["experts_in"][first:first + 2],
                         experts_out=p["experts_out"][first:first + 2])
            layer = ExpertLayer(whole.hybrid_config(dict(
                cfg, num_experts=2, experts_held={"first": first})))
            out, reports = layer.apply({"params": share}, u,
                                       mutable=["reports"])
            assert np.array_equal(
                reports["reports"]["expert_counts"], counts)
            # the reference's share is the program's
            whole.use(dict(cfg, num_experts=2,
                           experts_held={"first": first}))
            _close(out, whole._experts_layer(share, u)[0], rtol=1e-5)
            total = total + out
    assert int(counts.sum()) == 2 * 40 * 3
    _close(total, want, rtol=1e-5)


# --------------------------------------------- the model and the reference
def test_the_stack_builds_the_references_tree(ref):
    """`c A c c c`, the first layer with the dense MLP: the program's
    parameter tree is the reference's, shape by shape, and counts what
    `short_conv_ops.parameters` counts."""
    mod, cfg = ref
    model = SensorHybrid(mod.hybrid_config(cfg))
    assert model.cfg.layer_types == ("short_conv", "attention") \
        + ("short_conv",) * 3
    assert model.cfg.ffn_types == ("dense_ffn",) + ("moe_ffn",) * 4
    shapes = jax.tree.map(jnp.shape, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _batch()[0])["params"])
    assert shapes == jax.tree.map(jnp.shape, mod.init_params(3))
    assert sorted(shapes["layer0"]) == ["mixer", "mlp_in", "mlp_out",
                                        "norm1", "norm2"]
    assert sorted(shapes["layer1"]["mixer"]) == ["k", "k_norm", "o", "q",
                                                 "q_norm", "v"]
    assert sorted(shapes["layer3"]["moe"]) == [
        "experts_in", "experts_out", "router", "router_bias"]
    spec = importlib.util.spec_from_file_location(
        "bench_short_conv_ops", os.path.join(ROOT, "benchmark",
                                             "short_conv_ops.py"))
    ops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ops)
    assert ops.parameters(cfg) == sum(
        int(np.prod(s)) for s in jax.tree.leaves(
            shapes, is_leaf=lambda s: isinstance(s, tuple)))
    with pytest.raises(ValueError, match="known kinds"):
        SensorHybrid(HybridConfig(layer_types=("short_conv", "conv"))).init(
            jax.random.PRNGKey(0), _batch()[0])


@pytest.mark.parametrize("mode", ["dense", "flash_interpret"])
def test_model_matches_the_plain_reference(ref, mode):
    """Loss and every gradient leaf from the same seeded weights."""
    from iotml.train.loop import make_loss_fn

    mod, cfg = ref
    x, y, mask = _batch()
    params = mod.init_params(3)
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode=mode)
    loss = make_loss_fn(model, supervised=True)
    with jax.default_matmul_precision("highest"):
        (got, aux), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params, x, y, mask)
        want, wants = jax.jit(jax.value_and_grad(mod.loss_fn))(
            params, x, y, mask)
    assert float(abs(got - want)) <= 1e-5 * float(want)
    _close(grads, wants)
    for i in (0, 2, 3, 4):
        assert np.asarray(grads[f"layer{i}"]["mixer"]["conv_kernel"]).any()
    assert [int(c.sum()) for c in jax.tree.leaves(aux[2])] == [2 * 40 * 3] * 4


def test_two_step_fit_matches_the_reference(ref):
    """`Trainer.fit_compiled` → the scanned fit, two Adam steps an
    epoch, against the reference's fit written out: losses, updated
    parameters, both moments — and the expert counts read back with
    them against the reference's router."""
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
            _, first = mod._km._loss_counts(params, *(v[0] for v in stacked))
    finally:
        cfg["model"]["optimizer"]["learning_rate"] = 1e-5
    np.testing.assert_allclose(history["loss"], losses, rtol=1e-5)
    adam = trainer.state.opt_state[0]
    _close(jax.tree.map(lambda a, b: a - b, trainer.state.params, params),
           jax.tree.map(lambda a, b: a - b, p, params), rtol=2e-3)
    _close(adam.mu, mu)
    _close(adam.nu, nu)
    layers = history["reports"]["reports"]
    counts = [np.asarray(jax.tree.leaves(layers[f"layer{i}"])[0])
              for i in (1, 2, 3, 4)]
    assert [c.shape for c in counts] == [(2, 2, 16)] * 4
    assert np.array_equal(np.stack([c[0, 0] for c in counts]), first)


@pytest.mark.parametrize("mode,in_kernel", [("flash_interpret", 2),
                                            ("dense", 0)])
def test_a_fit_at_heads_that_fill_the_lanes_says_which_form_turned(
        mode, in_kernel):
    """Eight normed heads of 16 on eight key/value heads at a width of
    128 — `H·D = G·D` = one 128-lane tile, eight heads a chunk: under
    the kernels q and k are turned by `iotml_rope` on `[B, T, H·D]`
    after the heads' norms (`iotml_attn_rotary_kernel` 2), under `dense`
    by the pair form (0), and the compiled job's losses are the
    reference's either way."""
    from iotml.data.dataset import Batch
    from iotml.obs.metrics import default_registry
    from iotml.train.loop import Trainer

    mod, cfg = _reference("bench_lfm2_lanes_" + mode, hidden_size=128,
                          num_attention_heads=8, num_key_value_heads=8)
    cfg["model"]["optimizer"]["learning_rate"] = 1e-3
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
    assert got["iotml_attn_qk_norm"] == 1
    np.testing.assert_allclose(history["loss"], losses, rtol=1e-4)


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time counters after a fit — the layers by kind, the
    convolution's taps and that its kernels applied no activation, the
    attention's norms and turned features, the shared expert's width
    (none) — the new scopes in the fit's program, and the fit held to
    ONE `device_get`."""
    from iotml.data.dataset import Batch
    from iotml.obs.metrics import default_registry
    from iotml.train import loop
    from iotml.train.loop import Trainer

    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    jax.clear_caches()
    gets = []
    device_get = jax.device_get
    monkeypatch.setattr(loop.jax, "device_get",
                        lambda t: gets.append(1) or device_get(t))
    x, y, _ = _batch()
    trainer = Trainer(SensorHybrid(mod.hybrid_config(cfg)), supervised=True,
                      learning_rate=1e-5)
    history = trainer.fit_compiled(
        [Batch(x=np.asarray(x), y=np.asarray(y), n_valid=2,
               first_index=0)] * 3, epochs=2)
    got = default_registry.collect()
    assert history["fit"] == "scanned" and np.isfinite(history["loss"]).all()
    assert len(gets) == 1          # the reports came back with the losses
    assert [got[f'iotml_model_layers{{kind="{k}"}}'] for k in
            ("short_conv", "attention", "mamba", "mla", "dense_ffn",
             "moe_ffn")] == [4, 1, 0, 0, 1, 4]
    assert got["iotml_remat_blocks"] == 5
    assert got["iotml_conv_taps"] == 3
    assert got["iotml_conv_activation_fused"] == 0
    # one run of 64 channels, whole sublane tiles; 40 positions fill no
    # lane tile, so x is padded (and dy with it in the backward)
    assert got["iotml_conv_block_c"] == 64 and got["iotml_conv_block_t"] == 128
    assert (got['iotml_conv_grid_steps{kernel="fwd"}'],
            got['iotml_conv_grid_steps{kernel="bwd"}']) == (2, 2)
    assert (got['iotml_conv_operand_copies{kernel="fwd"}'],
            got['iotml_conv_operand_copies{kernel="bwd"}']) == (1, 2)
    assert got["iotml_attn_rotary_dim"] == 16
    # `dense` attention (and four heads of 16 fill no 128-lane tile):
    # XLA's pair form turned them
    assert got["iotml_attn_rotary_kernel"] == 0
    assert got["iotml_attn_qk_norm"] == 1
    assert got["iotml_moe_shared_dim"] == 0
    assert got['iotml_moe_experts{kind="held"}'] == 4
    assert got['iotml_moe_experts{kind="routed_over"}'] == 16
    assert got["iotml_moe_top_k"] == 3
    assert got["iotml_moe_dispatch_rows"] == moe.dispatch_rows(80, 3, 4)
    assert [got[f'iotml_remat_kept_bytes{{kind="{k}"}}'] for k in
            ("router", "experts", "flash", "latent_qk")] \
        == [4 * moe.plan_kept_bytes(80, 3, 4, 16), 0, 0, 0]
    # and under the byte budget the one dense MLP's first product
    # [80, 2 x 96]: an expert layer without a shared expert makes none
    assert got['iotml_remat_kept_bytes{kind="ffn"}'] == 80 * 192 * 4
    assert got['iotml_remat_kept_layers{kind="ffn"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn"}'] == 1
    # no post norms: a part's output is no candidate
    assert all(got[f'iotml_remat_{what}{{kind="{kind}"}}'] == 0
               for what in ("kept_bytes", "kept_layers", "keepable_layers")
               for kind in ("ffn_out", "mixer_out"))
    # the scopes ride the program's operations
    model = SensorHybrid(mod.hybrid_config(cfg))
    text = jax.jit(lambda p: model.apply(
        {"params": p}, x, mutable=["reports"])[0]).lower(
            mod.init_params(1)).as_text(debug_info=True)
    for scope in ("conv_proj", "short_conv", "attn", "rope", "qk_norm",
                  "router", "experts", "mlp"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    assert "/shared/" not in text
    # the accepted stacks' layers say what they are
    jax.clear_caches()
    SensorHybrid(HybridConfig(ffn_types=("moe_ffn",) * 3)).init(
        jax.random.PRNGKey(0), x)
    got = default_registry.collect()
    assert got["iotml_attn_rotary_dim"] == got["iotml_attn_qk_norm"] \
        == got["iotml_attn_rotary_kernel"] == 0
    assert got["iotml_moe_shared_dim"] == 32
    assert got["iotml_conv_taps"] == 4
    assert got["iotml_conv_activation_fused"] == 1
