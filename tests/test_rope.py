"""Rotary positions in the flash kernels' layout (`ops.rope`, the Pallas
call `iotml_rope`) against the pair form it stands in for
(`ops.moe.rotary`): value and gradient, ragged blocks, the shapes it
cannot take; and `GroupedAttention`'s choice between the two — by
`attn_mode` and the heads' lanes — with the layer's output and
gradients the `dense` path's either way.  All under the interpreter on
the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.models import hybrid
from iotml.models.hybrid import GroupedAttention, HybridConfig, SensorHybrid
from iotml.obs.metrics import default_registry
from iotml.ops import moe, rope

THETA = 1e6     # both configurations' `rope_theta`


def _x(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _blocks(monkeypatch, max_rows, pass_elems):
    """Blocks and passes small enough to be many in a few dozen rows."""
    monkeypatch.setattr(rope, "_MAX_ROWS", max_rows)
    monkeypatch.setattr(rope, "_PASS_ELEMS", pass_elems)
    jax.clear_caches()


SHAPES = [
    (1, 16, 128),    # `ou-train-backlog`'s q and k heads: a head a chunk
    (2, 32, 64),     # `lf-train-backlog`'s query heads: two heads a chunk
    (2, 8, 64),      # and its key heads
    (1, 2, 256),     # a head of two tiles' lanes
]
ROWS = [
    (40, 1024),    # a short window: one block, one pass
    (48, 16),      # three whole blocks of 16 rows, two passes each
    (44, 16),      # not a multiple of the block: the last one ragged
]


@pytest.mark.parametrize("T,max_rows", ROWS)
@pytest.mark.parametrize("B,H,D", SHAPES)
def test_the_call_is_the_pair_form(monkeypatch, B, H, D, T, max_rows):
    """Within 1e-6 of `moe.rotary` in float32, shape and dtype kept,
    position 0 unchanged and every head's norm kept."""
    _blocks(monkeypatch, max_rows, 8 * H * D)
    x = _x((B, T, H, D))
    got = rope.rope(x, rope.tables(T, D, THETA), interpret=True)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(got, moe.rotary(x, THETA), atol=1e-6, rtol=0)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-6)


@pytest.mark.parametrize("T,max_rows", ROWS)
@pytest.mark.parametrize("B,H,D", SHAPES)
def test_the_calls_gradient_is_the_pair_forms(monkeypatch, B, H, D, T,
                                              max_rows):
    """The backward is the same call turned back, on the cotangent:
    `jax.grad` of `moe.rotary` to 1e-6; the tables take no gradient."""
    _blocks(monkeypatch, max_rows, 8 * H * D)
    x, w = _x((B, T, H, D)), _x((B, T, H, D), seed=1)
    cos_sin = rope.tables(T, D, THETA)

    def turned(x, cos_sin):
        return jnp.sum(rope.rope(x, cos_sin, interpret=True) * w)

    got, for_tables = jax.grad(turned, argnums=(0, 1))(x, cos_sin)
    want = jax.grad(lambda x: jnp.sum(moe.rotary(x, THETA) * w))(x)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    assert not any(np.asarray(t).any() for t in for_tables)
    # and a turn back undoes a turn: the rotation's transpose
    back = jax.grad(lambda x: jnp.sum(
        rope.rope(x, cos_sin, interpret=True) * rope.rope(
            w, cos_sin, interpret=True)))(x)
    np.testing.assert_allclose(back, w, atol=2e-6, rtol=0)


def test_the_call_keeps_a_narrower_dtype():
    """bfloat16 in, bfloat16 out, turned in float32 as `rotary` turns."""
    x = _x((1, 32, 2, 128)).astype(jnp.bfloat16)
    got = rope.rope(x, rope.tables(32, 128, THETA), interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(moe.rotary(x, THETA), np.float32))


def test_the_call_takes_the_flat_stream_as_it_takes_the_heads():
    x = _x((2, 24, 4, 64))
    cos_sin = rope.tables(24, 64, THETA)
    flat = rope.rope(x.reshape(2, 24, 256), cos_sin, interpret=True)
    np.testing.assert_array_equal(
        flat.reshape(x.shape), rope.rope(x, cos_sin, interpret=True))


@pytest.mark.parametrize("width,R,lanes", [
    (2048, 128, 128), (2048, 64, 128), (512, 64, 128), (128, 16, 128),
    (1024, 256, 256),
    (192, 64, 0),      # heads that fill no whole 128-lane tile
    (64, 16, 0),       # the tiny presets' four heads of 16
    (384, 192, 0),     # a head neither a divisor nor a multiple of 128
    (630, 63, 0),      # an odd head has no pairs
])
def test_the_lanes_say_where_the_call_can_run(width, R, lanes):
    assert rope.lanes(width, R) == lanes
    if lanes:
        cos, sin = rope.tables(2, R, THETA)
        assert cos.shape == sin.shape == (2, lanes)
        # the sine's sign rides the table (position 1: angles in (0, 1])
        assert (np.asarray(sin[1, 0::2]) < 0).all() \
            and (np.asarray(sin[1, 1::2]) > 0).all()
    elif rope.lanes(max(R, 128), R):
        # heads the call could turn, at a width that is no whole chunks
        with pytest.raises(ValueError, match="iotml_rope"):
            rope.rope(jnp.zeros((1, 8, width // R, R)),
                      rope.tables(8, R, THETA), interpret=True)
    else:
        with pytest.raises(ValueError, match="iotml_rope"):
            rope.tables(8, R, THETA)


def test_tables_of_another_window_are_refused():
    with pytest.raises(ValueError, match="iotml_rope"):
        rope.rope(jnp.zeros((1, 16, 2, 64)), rope.tables(8, 64, THETA),
                  interpret=True)


# ------------------------------------------- the layer's choice of a form
def _layer(mode, H, G, D, qk_norm):
    cfg = HybridConfig(d_model=64, num_heads=H, num_kv_heads=G,
                       head_dim=D, attn_rope_theta=THETA, qk_norm=qk_norm)
    return GroupedAttention(cfg, mode)


CHOICES = [
    ("flash_interpret", 8, 8, 16, 2),    # H·D = G·D = 128 lanes
    ("flash_interpret", 4, 2, 64, 2),    # grouped: 256 lanes beside 128
    ("dense", 8, 8, 16, 0),              # the plain path: XLA's pair form
    ("flash_interpret", 4, 4, 16, 0),    # H·D = 64: no whole tile
    ("flash_interpret", 8, 4, 16, 0),    # q could, k (64 lanes) cannot
]


@pytest.mark.parametrize("qk_norm", [False, True])
@pytest.mark.parametrize("mode,H,G,D,in_kernel", CHOICES)
def test_grouped_attention_chooses_by_mode_and_shape(monkeypatch, mode, H, G,
                                                     D, in_kernel, qk_norm):
    """The gauge says which form turned q and k, the call ran for both
    or for neither — on the heads as the norm leaves them — and the
    layer's output and parameter gradients are the `dense` module's."""
    calls, turn = [], rope.rope
    monkeypatch.setattr(hybrid.rope, "rope", lambda x, *a, **kw: (
        calls.append(x.shape), turn(x, *a, **kw))[1])
    u = _x((2, 24, 64))
    params = _layer(mode, H, G, D, qk_norm).init(jax.random.PRNGKey(0), u)
    if qk_norm:   # unit weights would hide a norm on the wrong operand
        for i, name in enumerate(("q_norm", "k_norm")):
            params["params"][name]["scale"] = 0.5 + _x((D,), seed=i) ** 2
    got = default_registry.collect()
    assert got["iotml_attn_rotary_kernel"] == in_kernel
    assert got["iotml_attn_rotary_dim"] == D
    assert got["iotml_attn_qk_norm"] == qk_norm
    assert calls == [(2, 24, H, D), (2, 24, G, D)][:in_kernel]

    def loss(p, m):
        return jnp.sum(_layer(m, H, G, D, qk_norm).apply(p, u) ** 2)

    value, grads = jax.value_and_grad(loss)(params, mode)
    want, want_grads = jax.value_and_grad(loss)(params, "dense")
    np.testing.assert_allclose(value, want, rtol=2e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_attention_without_positions_turns_nothing():
    cfg = HybridConfig(d_model=64, num_heads=8, num_kv_heads=8, head_dim=16)
    assert hybrid.rotary_tables(cfg, "flash_interpret", 24) is None
    GroupedAttention(cfg, "flash_interpret").init(jax.random.PRNGKey(0),
                                                 _x((1, 24, 64)))
    got = default_registry.collect()
    assert got["iotml_attn_rotary_kernel"] == got["iotml_attn_rotary_dim"] \
        == 0


@pytest.mark.parametrize("loop_steps", [1, 3])
def test_a_stack_makes_its_tables_once(monkeypatch, loop_steps):
    """Two attention layers, `loop_steps` passes, forward and the
    blocks' recomputed backward: the tables are made ONCE where the step
    is traced and every application is handed them — the model's output
    and gradients the `dense` stack's."""
    made, tables = [], rope.tables
    monkeypatch.setattr(hybrid.rope, "tables", lambda *a: (
        made.append(a), tables(*a))[1])
    cfg = HybridConfig(
        d_model=128, num_heads=8, num_kv_heads=8, head_dim=16, mlp_dim=96,
        layer_types=("attention",) * 2, attn_rope_theta=THETA,
        loop_steps=loop_steps)
    x = _x((2, 24, 18))
    params = SensorHybrid(cfg, attn_mode="dense").init(
        jax.random.PRNGKey(0), x)
    assert made == []

    def loss(p, mode):
        out = SensorHybrid(cfg, attn_mode=mode).apply(p, x)
        return jnp.sum((out[0] if loop_steps > 1 else out) ** 2)

    value, grads = jax.value_and_grad(loss)(params, "flash_interpret")
    assert made == [(24, 16, THETA)]
    assert default_registry.collect()["iotml_attn_rotary_kernel"] == 2
    want, want_grads = jax.value_and_grad(loss)(params, "dense")
    assert default_registry.collect()["iotml_attn_rotary_kernel"] == 0
    np.testing.assert_allclose(value, want, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
