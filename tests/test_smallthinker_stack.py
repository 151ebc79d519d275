"""The global-and-sliding-window stack (`models.hybrid.SensorHybrid` with
`attention` and `window_attention` mixers whose rotary positions are a
layer's, and expert layers whose softmax-over-selected router reads the
block's own input ahead of the mixer, ReLU-gated experts, no shared one):
each new part against the equations of the benchmark's plain reference
(`stacks.reference`), outputs and every gradient, at 40 positions — past
the window of 24 and no multiple of a block; the router's input (a
planted swap fails); the chip's-share cut of the expert layer (the four
shares add up to the uncut layer); and what a fit says of the new parts.
The tree, the model and one compiled job's losses, update and Adam
moments against the reference are the `smallthinker` cases of
`test_stack_contract.py`.  All at a tiny preset on the CPU."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import HybridBlock, SensorHybrid
from iotml.models.latent_moe import ExpertLayer
from iotml.ops import moe
from stacks import batch as _batch
from stacks import close as _close
from stacks import stream as _stream
from stacks import value_and_grads as _value_and_grads


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("smallthinker")


def _layer_params(mod, seed, layer):
    return jax.jit(lambda k: mod._init(k))(jax.random.PRNGKey(seed))[layer]


def _attn(cfg, mode, turns, slides):
    return hybrid.GroupedAttention(cfg, mode, bool(turns),
                                   slides * cfg.attn_window)


# ------------------------------------------- the parts and their equations
@pytest.mark.parametrize("mode", ["dense", "flash_interpret"])
@pytest.mark.parametrize("turns,slides", [(0, 0), (1, 1)],
                         ids=["global", "window"])
def test_both_attention_layers_match_the_references_equations(
        ref, mode, turns, slides):
    """The global layer — no positions, every key of the causal past —
    and the window layer — heads turned, the last 24 keys — four heads on
    two key/value heads: the output and every gradient, through either
    attention (the band's tiles interpreted) against the reference's
    mask written out."""
    mod, cfg = ref
    m = mod.hybrid_config(cfg)
    p = _layer_params(mod, 4, "layer1")["mixer"]
    assert sorted(p) == ["k", "o", "q", "v"]
    # scores of order one, as at the published widths: under seeded
    # kernels 0.02 wide a head of 16 attends almost evenly, and neither
    # a turn nor a window would show
    p = dict(p, q={"kernel": 8 * p["q"]["kernel"]},
             k={"kernel": 8 * p["k"]["kernel"]})
    u = _stream(seed=5)
    attn = _attn(m, mode, turns, slides)
    got = _value_and_grads(lambda p, u: attn.apply({"params": p}, u), p, u)
    want = _value_and_grads(
        lambda p, u: mod._attention(p, u, turns, slides), p, u)
    _close(got, want)
    with jax.default_matmul_precision("highest"):
        mine = attn.apply({"params": p}, u)
        # the other layer's positions, and the other layer's mask, are
        # another function: a turn in the global layer or none in the
        # window layer, a window left out or put in, all show
        for other in ((1 - turns, slides), (turns, 1 - slides)):
            assert float(jnp.abs(_attn(m, mode, *other).apply(
                {"params": p}, u) - mine).max()) \
                > 1e-2 * float(jnp.abs(mine).max())
        # a key the window has left moves nothing: position 39 meets
        # keys 16..39 in a window layer, all of them in the global one
        moved = attn.apply({"params": p}, u.at[:, 10].add(1.0)) - mine
    reach = np.abs(np.asarray(moved)).max(axis=(0, 2)) > 1e-7
    assert not reach[:10].any() and reach[10]
    assert reach[10 + 24:].any() == (not slides)


def test_the_global_layer_is_the_attention_layer_the_stack_had(ref):
    """Without positions and without a window the layer is the grouped
    attention the accepted stacks run: the same module, told nothing."""
    mod, cfg = ref
    m = mod.hybrid_config(cfg)
    p = _layer_params(mod, 4, "layer0")["mixer"]
    u = _stream(seed=6)
    plain = hybrid.GroupedAttention(
        dataclasses.replace(m, attn_rope_theta=0.0), "dense")
    assert np.array_equal(plain.apply({"params": p}, u),
                          _attn(m, "dense", 0, 0).apply({"params": p}, u))
    assert [m.turns(i) for i in range(4)] == [False, True, True, True]


def _routed_then_applied(layer, p, u, h):
    """The expert layer as the block calls it: routed on `h`, the
    experts on `u`."""
    def call(mdl):
        return mdl(u, mdl(h, plan_only=True))
    return nn.apply(call, layer, mutable=["reports"])({"params": p})


def test_expert_layer_routed_on_another_input_matches_the_reference(
        ref, monkeypatch):
    """The router reads one array and the experts another: the three
    largest raw logits of `h W_g` (the tiny preset's six), a softmax
    over those three, ReLU-gated experts on `u`, no shared expert and no
    bias — the tiles against the reference's dense-masked experts, the
    output, every gradient (to both inputs) and the counts."""
    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    p = _layer_params(mod, 6, "layer2")["moe"]
    assert sorted(p) == ["experts_in", "experts_out", "router"]
    u, h = _stream(seed=7), _stream(seed=8)
    layer = ExpertLayer(mod.hybrid_config(cfg))
    both = jnp.stack([u, h])
    w = _stream(seed=99)

    def value_and_grads(f):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p, both: jnp.sum(w * f(p, both[0], both[1])),
                argnums=(0, 1)))(p, both)

    got = value_and_grads(
        lambda p, u, h: _routed_then_applied(layer, p, u, h)[0])
    want = value_and_grads(lambda p, u, h: mod._experts_layer(p, u, h)[0])
    _close(got, want)
    assert np.asarray(got[1][1][1]).any()    # the router's input's
    with jax.default_matmul_precision("highest"):
        _, reports = _routed_then_applied(layer, p, u, h)
        experts, weights, counts = mod._route(p, h.reshape(80, 64))
        # a softmax over all sixteen, renormalised over the selected
        full = jax.nn.softmax(h.reshape(80, 64) @ p["router"], axis=-1)
        picked = jnp.take_along_axis(full, experts, axis=-1)
    assert np.array_equal(reports["reports"]["expert_counts"], counts)
    assert int(counts.sum()) == 2 * 40 * 3
    np.testing.assert_allclose(
        weights, picked / picked.sum(axis=-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(axis=-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("layer", [0, 1], ids=["global", "window"])
def test_the_block_routes_on_its_own_input_and_a_swap_shows(ref, layer):
    """A whole block against the reference's: the router on the block's
    input `h`, un-normed and ahead of attention, the experts on
    `RMSNorm(h + attention)` — output and every gradient; and the same
    block with the router on the normed stream the experts read (the
    planted swap) is another function."""
    mod, cfg = ref
    m = mod.hybrid_config(cfg)
    p = stacks.unsettled(_layer_params(mod, 9, f"layer{layer}"), 9)
    h = _stream(seed=10)
    turns, slides = mod._layouts()[layer]

    def block(m):
        made = HybridBlock(m.layer_types[layer], m, "dense", "moe_ffn",
                           m.turns(layer))
        return lambda p, h: made.apply({"params": p}, h,
                                       mutable=["reports"])[0]

    want = _value_and_grads(
        lambda p, h: mod._block(p, h, turns, slides)[0], p, h)
    _close(_value_and_grads(block(m), p, h), want)
    swapped = _value_and_grads(
        block(dataclasses.replace(m, router_input="ffn")), p, h)
    with pytest.raises(AssertionError):
        _close(swapped, want, rtol=1e-2)


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """The expert layer at a small size — 16 experts, 3 a token — and its
    cut into four shares of four: every share routes over all sixteen on
    the block's input, and alike; each computes its own experts' terms;
    with no shared expert there is no part every chip computes alike, so
    the four routed sums, nothing counted once, add up to the uncut
    reference's layer (the reference's own functions, handed all
    sixteen)."""
    whole, cfg = stacks.tiny("smallthinker", "bench_smallthinker_uncut",
                             moe_num_primary_experts=16)
    u, h = _stream(seed=11), _stream(seed=12)
    p = _layer_params(whole, 11, "layer2")["moe"]
    assert p["experts_in"].shape[0] == 16
    with jax.default_matmul_precision("highest"):
        want, counts = whole._experts_layer(p, u, h)
        total = jnp.zeros_like(u)
        for first in range(0, 16, 4):
            share = dict(p, experts_in=p["experts_in"][first:first + 4],
                         experts_out=p["experts_out"][first:first + 4])
            held = dict(cfg, moe_num_primary_experts=4,
                        experts_held={"first": first})
            layer = ExpertLayer(whole.hybrid_config(held))
            out, reports = _routed_then_applied(layer, share, u, h)
            assert np.array_equal(
                reports["reports"]["expert_counts"], counts)
            # the reference's share is the program's
            whole.use(held)
            _close(out, whole._experts_layer(share, u, h)[0], rtol=1e-5)
            total = total + out
    assert int(counts.sum()) == 2 * 40 * 3
    _close(total, want, rtol=1e-5)


# --------------------------------------------------- the share's load
@pytest.mark.parametrize("counts,chips,want", [
    # by hand: 9 and 7 open a chip each; 6 joins the 7, 5 the 9, 4 the
    # 13, 3 and then 2 the chip that has least and still has room, 1 what
    # is left: loads 19 and 18 around a mean of 18.5, the first chip's
    ([9, 7, 6, 5, 4, 3, 2, 1], 2, [0, 3, 5, 6]),
    # one expert takes everything: its chip's share is as far from the
    # mean as it can be, and the first idle chip's the nearest
    ([100, 0, 0, 0, 0, 0, 0, 0], 4, [1, 2]),
    # an even router: any share is the mean's, the first chip's it is
    ([5] * 8, 4, [0, 4]),
])
def test_the_balanced_share_by_hand(ref, counts, chips, want):
    assert ref[0]._balanced_share(counts, chips).tolist() == want


@pytest.mark.parametrize("seed", range(6))
def test_the_balanced_share_is_a_whole_chips_and_near_the_mean(ref, seed):
    """Skewed counts over 64 experts and four chips: the share is
    sixteen distinct experts in order, and dealing the busiest first to
    the chip with the least load leaves no chip further from the mean
    than the busiest expert it cannot split."""
    rng = np.random.default_rng(seed)
    counts = np.sort(rng.pareto(1.2, 64) * 1000).astype(np.int64)[::-1]
    counts = rng.permutation(counts)
    share = ref[0]._balanced_share(counts, 4)
    assert len(share) == len(set(share.tolist())) == 16
    assert share.tolist() == sorted(share.tolist())
    rest = np.setdiff1d(np.arange(64), share)
    assert abs(counts[share].sum() * 4 - counts.sum()) \
        <= abs(counts[rest[:16]].sum() * 4 - counts.sum()) + 4 * counts.max()
    assert abs(counts[share].sum() - counts.sum() / 4) <= counts.max()


def test_the_placement_relabels_the_routers_outputs_and_nothing_else(ref):
    """The experts placed on a batch: every router's columns are the
    seeded ones in another order (held and absent experts each in their
    old order), every other leaf is the seeded leaf itself, the held
    share's load on that batch is within one expert's of the balanced
    one in every layer."""
    mod, cfg = ref
    seeded = mod._km.init_params(7)
    x = _batch(T=40, seed=7)[0]
    with jax.default_matmul_precision("highest"):
        placed = mod._place(seeded, x)
        _, counts = mod._forward(placed, x)
    moved = 0
    for name, layer in seeded.items():
        if not name.startswith("layer"):
            assert placed[name] is layer
            continue
        was, now = np.asarray(layer["moe"]["router"]), \
            np.asarray(placed[name]["moe"]["router"])
        order = [int(np.flatnonzero((was == now[:, [j]]).all(0))[0])
                 for j in range(16)]
        assert sorted(order) == list(range(16))
        assert order[:4] == sorted(order[:4]) and order[4:] == sorted(order[4:])
        moved += order != list(range(16))
        for part in ("norm1", "norm2", "mixer"):
            assert placed[name][part] is layer[part]
        for leaf in ("experts_in", "experts_out"):
            assert placed[name]["moe"][leaf] is layer["moe"][leaf]
    assert moved
    for c in counts:
        c = np.asarray(c)
        assert int(c.sum()) == 2 * 40 * 3
        assert abs(int(c[:4].sum()) - int(c.sum()) / 4) <= int(c.max())


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time counters after a fit under the kernels — the layers
    by kind, the window, the router's form and input, the experts' form,
    and the flash geometry BY KERNEL AND BY MASK: one program holds a
    causal call and band calls, and says both — the new scopes in the
    fit's program, and the fit held to ONE `device_get`."""
    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    x = _batch()[0]
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode="flash_interpret")
    _, _, got, gets = stacks.tiny_fit(model, monkeypatch)
    assert gets == 1          # the reports came back with the losses
    assert [got[f'iotml_model_layers{{kind="{k}"}}'] for k in
            ("window_attention", "attention", "short_conv", "mamba", "mla",
             "dense_ffn", "moe_ffn")] == [3, 1, 0, 0, 0, 0, 4]
    assert got["iotml_remat_blocks"] == 4
    # the last traced grouped-attention layer is a window layer
    assert got["iotml_attn_window"] == 24
    assert got["iotml_attn_rotary_dim"] == 16
    # four heads of 16 fill no 128-lane tile: XLA's pair form turned them
    assert got["iotml_attn_rotary_kernel"] == 0
    assert got["iotml_attn_qk_norm"] == 0
    assert [got[f'iotml_moe_router_form{{kind="{k}"}}']
            for k in moe.ROUTER_FORMS] == [0, 1]
    assert (got['iotml_moe_router_input{kind="ffn"}'],
            got['iotml_moe_router_input{kind="block"}']) == (0, 1)
    assert [got[f'iotml_moe_expert_form{{kind="{k}"}}']
            for k in ("gated_silu", "relu2", "relu_gated")] == [0, 0, 1]
    assert got["iotml_moe_shared_dim"] == 0
    assert got['iotml_moe_experts{kind="held"}'] == 4
    assert got['iotml_moe_experts{kind="routed_over"}'] == 16
    assert got["iotml_moe_top_k"] == 3
    assert got['iotml_remat_kept_bytes{kind="router"}'] \
        == 4 * moe.plan_kept_bytes(80, 3, 4, 16)
    assert got['iotml_remat_kept_bytes{kind="flash"}'] \
        == 4 * 80 * 4 * (16 * 4 + 4)
    stacks.only_these_kinds_are_kept(got, "router", "flash")
    # 40 positions in one 128² tile either way: the masks' areas differ
    # the backward is the one kernel at this shape
    assert got["iotml_flash_backward_fused"] == 1
    for kernel in ("fwd", "bwd_fused"):
        said = {mask: [got[f'iotml_flash_mask_{what}{{kernel="{kernel}",'
                           f'kind="{mask}"}}']
                       for what in ("window", "tiles", "walked_area",
                                    "live_area")]
                for mask in ("causal", "band")}
        assert said == {"causal": [0, 1, 128 * 128, 40 * 41 // 2],
                        "band": [24, 1, 128 * 128,
                                 24 * 25 // 2 + 16 * 24]}, kernel
    stacks.scopes_in_the_program(
        model, mod.init_params(1), x, ("router", "attn", "rope", "experts"))
