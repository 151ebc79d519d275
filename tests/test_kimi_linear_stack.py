"""The delta-rule stack (`models.hybrid.SensorHybrid` with `kda` mixers —
Kimi Delta Attention, `ops.delta.kda_scan` — three to one beside latent
attention WITHOUT positions, a dense layer and sigmoid-routed experts
with a shared one): each new part against the equations of the
benchmark's plain reference (`stacks.reference`), which steps the
recurrence position by position — outputs and every gradient, at 40
positions, no multiple of the chunk of 16; the three planted faults,
each another function; the chip's-share cut of the expert layer (the
four shares add up to the uncut layer, the shared expert counted once);
the experts' placement; and what a fit says of the new parts.  The tree,
the model and one compiled job's losses, update and Adam moments against
the reference are the `kimi_linear` cases of `test_stack_contract.py`.
All at a tiny preset on the CPU."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import KdaMixer, SensorHybrid
from iotml.models.latent_moe import ExpertLayer, LatentAttention
from iotml.ops import moe
from stacks import batch as _batch
from stacks import close as _close
from stacks import stream as _stream
from stacks import value_and_grads as _value_and_grads


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("kimi_linear")


def _layer_params(mod, seed, layer):
    return jax.jit(lambda k: mod._init(k))(jax.random.PRNGKey(seed))[layer]


def _kda_params(mod, seed):
    """A KDA mixer's seeded weights with taps 50 times as wide — q̃ and k̃
    of order 0.1 and not 0.003, far over the L2 norms' ε — and a heads'
    norm whose weight is not one."""
    p = stacks.unsettled(_layer_params(mod, seed, "layer0"), seed)["mixer"]
    return dict(p, conv_kernel=50 * p["conv_kernel"])


#: the benchmark's planter of the faults a check has to refuse
#: (`benchmark/tests/planted_faults.py`, which the chip runs by hand)
planted_faults = stacks.load_module("bench_planted_faults", os.path.join(
    os.path.dirname(stacks.CONFIGS), "tests", "planted_faults.py"))


# ------------------------------------------- the parts and their equations
def test_the_kda_mixer_is_the_references_stepped_recurrence(ref):
    """Projections, the three convolutions (the Pallas kernels,
    interpreted), the L2 norms, both gates, the chunked delta rule and
    the heads' gated norm against the reference's mixer written out and
    stepped: the output and every gradient."""
    mod, cfg = ref
    p, u = _kda_params(mod, 4), _stream(seed=5)
    assert sorted(p) == ["A_log", "conv_kernel", "dt_bias", "f_up", "g_up",
                         "gates_in", "norm", "o", "qkv"]
    mixer = KdaMixer(mod.hybrid_config(cfg))
    got = _value_and_grads(lambda p, u: mixer.apply({"params": p}, u), p, u)
    want = _value_and_grads(mod._kda, p, u)
    _close(got, want)
    assert all(np.asarray(g).any() for g in jax.tree.leaves(got[1][0]))


@pytest.mark.parametrize("fault", ["scalar_gate", "no_delta"])
def test_a_planted_fault_in_the_rule_is_another_function(ref, fault,
                                                         monkeypatch):
    mod, cfg = ref
    p, u = _kda_params(mod, 4), _stream(seed=5)
    mixer = KdaMixer(mod.hybrid_config(cfg))
    want = _value_and_grads(mod._kda, p, u)
    planted_faults.plant(fault, monkeypatch.setattr)
    got = _value_and_grads(lambda p, u: mixer.apply({"params": p}, u), p, u)
    with pytest.raises(AssertionError):
        _close(got, want, rtol=1e-2)


@pytest.mark.parametrize("mode", ["dense", "flash_interpret"])
def test_latent_attention_without_positions_matches_and_a_turn_shows(
        ref, mode):
    """`mla_use_nope`: the 64-wide parts stay and nothing turns them —
    the reference's plain latent attention with the identity where the
    sparse-expert file's turns; the layer that DOES turn them (that
    file's) is another function."""
    mod, cfg = ref
    m = mod.hybrid_config(cfg)
    assert m.mla_rope is False
    p = _layer_params(mod, 6, "layer3")["mixer"]
    # scores of order one: seeded kernels 0.02 wide attend almost evenly
    p = dict(p, q={"kernel": 8 * p["q"]["kernel"]},
             kv_a={"kernel": 8 * p["kv_a"]["kernel"]})
    u = _stream(seed=7)
    attn = LatentAttention(m, mode)
    got = _value_and_grads(lambda p, u: attn.apply({"params": p}, u), p, u)
    want = _value_and_grads(mod._km._attention, p, u)
    _close(got, want)
    turned = LatentAttention(dataclasses.replace(m, mla_rope=True), mode)
    with jax.default_matmul_precision("highest"):
        mine = attn.apply({"params": p}, u)
        assert float(jnp.abs(turned.apply({"params": p}, u) - mine).max()) \
            > 1e-2 * float(jnp.abs(mine).max())
        # and the turned layer is the reference's turned layer
        mod._km._rotary = mod._turned
        try:
            _close(turned.apply({"params": p}, u),
                   mod._km._attention(p, u), rtol=1e-4)
        finally:
            mod._km._rotary = lambda x: x


def test_the_stack_builds_no_rotary_turn(ref, monkeypatch):
    """No `rope` scope in the program and no call of `ops.moe.rotary`
    while it is traced: the latent layer of this stack has no positions
    to apply."""
    mod, cfg = ref

    def never(*a, **k):
        raise AssertionError("a rotary turn in a stack without positions")

    monkeypatch.setattr(moe, "rotary", never)
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode="flash_interpret")
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(model.apply(
        {"params": p}, x, mutable=["reports"])[0]))).lower(
            mod.init_params(1), _batch()[0]).as_text(debug_info=True)
    assert "rope" not in text and "mla_key" in text
    for scope in ("kda_proj", "kda_conv", "kda_norm", "kda_gates",
                  "kda_intra", "kda_state", "kda_out"):
        assert f"{scope}/" in text or f"/{scope}" in text, scope


# ------------------------------------------------ the chip's share
def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """The expert layer at a small size — 16 experts, 3 a token, one
    shared — and its cut into four shares of four: every share routes
    over all sixteen, and alike; each computes its own experts' terms and
    the shared expert; the four outputs less three of the four shared
    experts' (what every chip computes alike is counted ONCE) add up to
    the uncut reference's layer."""
    whole, cfg = stacks.tiny("kimi_linear", "bench_kimi_linear_uncut",
                             num_experts=16)
    u = _stream(seed=11)
    p = _layer_params(whole, 11, "layer2")["moe"]
    p = dict(p, router_bias=0.02 * jax.random.normal(
        jax.random.PRNGKey(3), (16,)))
    assert p["experts_in"].shape[0] == 16
    with jax.default_matmul_precision("highest"):
        want, counts = whole._km._experts_layer(p, u)
        shared = whole._km._gated(u, p["shared_in"]["kernel"],
                                  p["shared_out"]["kernel"])
        total = jnp.zeros_like(u)
        for first in range(0, 16, 4):
            share = dict(p, experts_in=p["experts_in"][first:first + 4],
                         experts_out=p["experts_out"][first:first + 4])
            held = dict(cfg, num_experts=4, experts_held={"first": first})
            layer = ExpertLayer(whole.hybrid_config(held))
            out, reports = layer.apply({"params": share}, u,
                                       mutable=["reports"])
            assert np.array_equal(
                reports["reports"]["expert_counts"], counts)
            # the reference's share is the program's
            _close(out, whole._km._experts_layer(share, u)[0], rtol=1e-5)
            total = total + out
    assert int(counts.sum()) == 2 * 40 * 3
    _close(total - 3 * shared, want, rtol=1e-5)


def test_the_placement_relabels_the_routing_layers_outputs_alone(ref):
    """The experts placed on a batch: each expert layer's router columns
    (and its bias's entries) are the seeded ones in another order, the
    dense layer and every other leaf are the seeded leaves themselves,
    and the held share's load on what that block's router reads is within
    one expert's of the balanced one."""
    mod, cfg = ref
    seeded = mod._km.init_params(7)
    x = _batch(T=40, seed=7)[0]
    with jax.default_matmul_precision("highest"):
        placed = mod._place(seeded, x)
        _, counts = mod._forward(placed, x)
    assert placed["layer0"] is seeded["layer0"] and "moe" not in \
        seeded["layer0"]
    moved = 0
    for i in range(1, 5):
        was, now = (np.asarray(t[f"layer{i}"]["moe"]["router"])
                    for t in (seeded, placed))
        order = [int(np.flatnonzero((was == now[:, [j]]).all(0))[0])
                 for j in range(16)]
        assert sorted(order) == list(range(16))
        assert order[:4] == sorted(order[:4]) \
            and order[4:] == sorted(order[4:])
        moved += order != list(range(16))
        for part in ("norm1", "norm2", "mixer"):
            assert placed[f"layer{i}"][part] is seeded[f"layer{i}"][part]
        for leaf in ("experts_in", "experts_out", "shared_in"):
            assert placed[f"layer{i}"]["moe"][leaf] \
                is seeded[f"layer{i}"]["moe"][leaf]
    assert moved and len(counts) == 4
    for c in counts:
        c = np.asarray(c)
        assert int(c.sum()) == 2 * 40 * 3
        assert abs(int(c[:4].sum()) - int(c.sum()) / 4) <= int(c.max())


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time gauges after a fit under the kernels — the layers
    by kind, the scan's chunking and the states it keeps, that the latent
    layer turned nothing — and the fit held to ONE `device_get`."""
    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode="flash_interpret")
    _, _, got, gets = stacks.tiny_fit(model, monkeypatch)
    assert gets == 1          # the reports came back with the losses
    assert [got[f'iotml_model_layers{{kind="{k}"}}'] for k in
            ("kda", "mla", "attention", "window_attention", "short_conv",
             "mamba", "dense_ffn", "moe_ffn")] == [4, 1, 0, 0, 0, 0, 1, 4]
    assert got["iotml_remat_blocks"] == 5
    assert got["iotml_model_mla_rope"] == 0
    assert got["iotml_kda_chunk_size"] == 16
    assert got["iotml_kda_chunks"] == 3            # ⌈40 / 16⌉
    # one segment a window: two states of 4 heads of 16 × 16, float32
    assert got["iotml_kda_state_bytes"] == 2 * 4 * 16 * 16 * 4
    assert got["iotml_conv_taps"] == 4
    assert got["iotml_conv_activation_fused"] == 1
    assert got['iotml_moe_experts{kind="held"}'] == 4
    assert got['iotml_moe_experts{kind="routed_over"}'] == 16
    assert got["iotml_moe_top_k"] == 3
    # an un-turned q is not kept: k alone, [2, 40, 4, 16 + 8] floats
    assert got['iotml_remat_kept_bytes{kind="latent_qk"}'] \
        == 80 * 4 * 24 * 4
    assert got['iotml_remat_kept_bytes{kind="flash"}'] \
        == 80 * 4 * (16 * 4 + 4)
    stacks.only_these_kinds_are_kept(got, "router", "flash", "latent_qk",
                                     "ffn")
