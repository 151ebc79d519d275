"""Attention stack: flash kernel (interpreted) and ring attention must match
the jnp reference exactly, including causal masking across shards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.ops import attention
from iotml.ops.attention import (attention_reference, blockwise_update,
                                 finalize_blockwise, flash_attention)
from iotml.parallel.mesh import make_mesh
from iotml.parallel.ring_attention import make_ring_attention


def _qkv(B=2, T=32, H=2, D=8, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)), dtype)  # noqa: E731
    return mk(), mk(), mk()


def test_reference_attention_is_causal():
    q, k, v = _qkv()
    out = attention_reference(q, k, v, causal=True)
    # changing future keys must not affect past outputs
    k2 = k.at[:, 20:].set(0.0)
    v2 = v.at[:, 20:].set(0.0)
    out2 = attention_reference(q, k2, v2, causal=True)
    np.testing.assert_allclose(out[:, :20], out2[:, :20], rtol=1e-6, atol=1e-6)


def test_blockwise_update_equals_reference():
    """Folding KV in 4 blocks through the online softmax == full softmax."""
    q, k, v = _qkv(T=32)
    B, T, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    o = jnp.zeros((B, T, H, D), jnp.float32)
    m = jnp.full((B, H, T), -1e30, jnp.float32)
    l = jnp.zeros((B, H, T), jnp.float32)
    qpos = np.arange(T)
    for blk in range(4):
        sl = slice(blk * 8, (blk + 1) * 8)
        kpos = np.arange(T)[sl]
        mask = jnp.asarray(qpos[:, None] >= kpos[None, :])
        o, m, l = blockwise_update(o, m, l, q, k[:, sl], v[:, sl], scale, mask)
    got = finalize_blockwise(o, l)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,block", [(32, 16), (40, 16)])
def test_flash_attention_interpreted_matches_reference(T, block):
    q, k, v = _qkv(T=T)
    got = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          interpret=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grads_match_reference():
    """Custom VJP (blockwise recompute backward) vs dense autodiff."""
    q, k, v = _qkv(T=40)
    f = lambda q, k, v: jnp.sum(  # noqa: E731
        jnp.sin(flash_attention(q, k, v, True, 16, 16, True)))
    r = lambda q, k, v: jnp.sum(  # noqa: E731
        jnp.sin(attention_reference(q, k, v, causal=True)))
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ring_attention_matches_reference():
    mesh = make_mesh((8,), ("seq",))
    q, k, v = _qkv(T=64)
    ring = make_ring_attention(mesh, "seq", causal=True)
    got = ring(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_non_causal():
    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    q, k, v = _qkv(T=32, seed=3)
    ring = make_ring_attention(mesh, "seq", causal=False)
    got = ring(q, k, v)
    want = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_output_is_seq_sharded():
    mesh = make_mesh((8,), ("seq",))
    q, k, v = _qkv(T=64)
    ring = make_ring_attention(mesh, "seq")
    out = ring(q, k, v)
    assert len(out.sharding.device_set) == 8


# ------------------------------------------ derived tiles (flash_geometry)
#: shapes that hit every branch of the rule: T below one tile, T not a
#: multiple of the tile, one tile a head with heads to group (B·H = 8),
#: a window several tiles long, both head widths, both dtypes, causal
#: and (at block-multiple T) not
DERIVED_CASES = [
    # B, T, H, D, dtype, causal
    (2, 100, 2, 64, jnp.float32, True),
    (2, 500, 2, 64, jnp.float32, True),
    (2, 256, 4, 64, jnp.float32, True),
    (2, 256, 4, 64, jnp.float32, False),
    (1, 256, 2, 128, jnp.bfloat16, True),
    (1, 500, 2, 128, jnp.bfloat16, True),
    (1, 1024, 2, 64, jnp.float32, True),
    (1, 1024, 2, 64, jnp.float32, False),
    (1, 1024, 2, 128, jnp.float32, True),
    (1, 1024, 2, 64, jnp.bfloat16, True),
]


@pytest.mark.parametrize("B,T,H,D,dtype,causal", DERIVED_CASES)
def test_flash_attention_derived_tiles_match_reference(B, T, H, D, dtype,
                                                       causal):
    """Forward and all three gradients with tiles the rule derives."""
    q, k, v = _qkv(B, T, H, D, dtype=dtype)
    up = lambda x: x.astype(jnp.float32)  # noqa: E731
    f = lambda q, k, v: jnp.sum(jnp.sin(up(  # noqa: E731
        flash_attention(q, k, v, causal=causal, interpret=True))))
    r = lambda q, k, v: jnp.sum(jnp.sin(  # noqa: E731
        attention_reference(up(q), up(k), up(v), causal=causal)))
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert got.dtype == dtype
    want = attention_reference(up(q), up(k), up(v), causal=causal)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    # float32: this file's tolerances; bfloat16: the output's own
    # rounding (8 bits of mantissa) against the float32 reference
    fwd_tol, grad_tol = (dict(rtol=2e-5, atol=2e-5),
                         dict(rtol=1e-4, atol=1e-5)) \
        if dtype == jnp.float32 else (dict(rtol=2e-2, atol=2e-2),) * 2
    np.testing.assert_allclose(np.asarray(up(got)), np.asarray(want),
                               **fwd_tol)
    for a, b in zip(gf, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(up(a)), np.asarray(up(b)),
                                   **grad_tol)


def test_non_causal_flash_attention_still_needs_whole_tiles():
    q, k, v = _qkv(1, 100, 2, 64)
    with pytest.raises(ValueError, match="non-causal"):
        flash_attention(q, k, v, causal=False, interpret=True)


RULE_SHAPES = [
    # T, D, itemsize, B·H, causal
    (100, 64, 4, 4, True), (500, 64, 4, 4, True), (256, 64, 4, 256, True),
    (256, 64, 4, 6, True), (1024, 64, 4, 64, True), (1024, 64, 4, 64, False),
    (1024, 128, 2, 2, True), (1152, 64, 4, 8, True), (4096, 128, 2, 32, True),
    (65536, 128, 2, 2, True), (65536, 64, 4, 4, True),
    (1_000_000, 128, 2, 2, True),
]


@pytest.mark.parametrize("kernel", attention.KERNELS)
@pytest.mark.parametrize("T,D,itemsize,bh,causal", RULE_SHAPES)
def test_flash_geometry_invariants(kernel, T, D, itemsize, bh, causal):
    g = attention.flash_geometry(kernel, T, D, itemsize, bh, causal)
    t_pad = -(-T // 128) * 128
    for block in (g.block_q, g.block_k):
        assert block % 128 == 0 and t_pad % block == 0
        assert block <= attention._MAX_BLOCK
    assert (g.t_q, g.t_k) == (t_pad, t_pad)
    assert bh % g.heads == 0
    assert attention._vmem_bytes(kernel, g.block_q, g.block_k, g.heads, D,
                                 itemsize) <= attention._VMEM_BUDGET
    nq, nk = g.t_q // g.block_q, g.t_k // g.block_k
    live = attention._tri_tile_count(nq, nk, g.block_q, g.block_k)
    assert g.tri == (causal and live <= attention._TRI_TILE_CAP)
    assert g.tiles == (live if g.tri else nq * nk)
    assert g.grid_steps == bh // g.heads * g.tiles
    if T <= 65536:
        # every practical length keeps the triangular grid: dead tiles
        # do not exist, and the maps stay SMEM-sized
        assert g.tri == causal and g.tiles <= attention._TRI_TILE_CAP


@pytest.mark.parametrize("kernel", attention.KERNELS)
def test_flash_geometry_explicit_blocks_win(kernel):
    g = attention.flash_geometry(kernel, 1024, 64, 4, 64, True, 128, 128)
    assert (g.block_q, g.block_k, g.heads, g.grid_steps) == (
        128, 128, 1, 64 * 36)
    # one named, the other derived around it; still one head a step
    g = attention.flash_geometry(kernel, 1024, 64, 4, 64, True, block_k=256)
    assert (g.block_k, g.heads) == (256, 1) and 1024 % g.block_q == 0
    # blocks that are no multiple of 128 pad T to themselves, as before
    g = attention.flash_geometry(kernel, 40, 8, 4, 4, True, 16, 16)
    assert (g.block_q, g.block_k, g.t_q, g.t_k) == (16, 16, 48, 48)
    # the backward's cap sits in the rule: the forward takes 2048 as
    # named, the backward kernels stop at the largest tile that compiles
    g = attention.flash_geometry(kernel, 65536, 128, 2, 2, True, 2048, 2048)
    want = 2048 if kernel == "fwd" else attention._MAX_BLOCK
    assert (g.block_q, g.block_k) == (want, want)
