"""The gated-short-convolution stack (`models.hybrid.SensorHybrid` with
`short_conv` mixers, grouped attention that norms and turns its queries
and keys, and expert layers without a shared expert): each new part
against the equations of the benchmark's plain reference
(`stacks.reference`), outputs and every gradient; the chip's-share cut
of the expert layer (the eight shares add up to the uncut layer: there
is no part every chip computes alike); and what a fit says of the new
parts.  The tree, the model and one compiled job against the reference
are the `lfm2` cases of `test_stack_contract.py`.  All at a tiny preset
on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import HybridConfig, SensorHybrid
from iotml.models.latent_moe import ExpertLayer
from iotml.ops import moe
from stacks import batch as _batch
from stacks import close as _close
from stacks import stream as _stream
from stacks import value_and_grads as _value_and_grads


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("lfm2")


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
    whole, cfg = stacks.tiny("lfm2", "bench_lfm2_uncut", num_experts=16)
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


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time counters after a fit — the layers by kind, the
    convolution's taps and that its kernels applied no activation, the
    attention's norms and turned features, the shared expert's width
    (none) — the new scopes in the fit's program, and the fit held to
    ONE `device_get`."""
    from iotml.obs.metrics import default_registry

    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    x = _batch()[0]
    model = SensorHybrid(mod.hybrid_config(cfg))
    _, _, got, gets = stacks.tiny_fit(model, monkeypatch)
    assert gets == 1          # the reports came back with the losses
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
    assert got['iotml_remat_kept_bytes{kind="router"}'] \
        == 4 * moe.plan_kept_bytes(80, 3, 4, 16)
    # and under the byte budget the one dense MLP's first product
    # [80, 2 x 96]: an expert layer without a shared expert makes none
    assert got['iotml_remat_kept_bytes{kind="ffn"}'] == 80 * 192 * 4
    assert got['iotml_remat_kept_layers{kind="ffn"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn"}'] == 1
    # and nothing else: `dense` attention ran no kernel
    stacks.only_these_kinds_are_kept(got, "router", "ffn")
    assert "/shared/" not in stacks.scopes_in_the_program(
        model, mod.init_params(1), x,
        ("conv_proj", "short_conv", "attn", "rope", "qk_norm", "router",
         "experts", "mlp"))
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
