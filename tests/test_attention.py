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


def _qkv(B=2, T=32, H=2, D=8, seed=0, dtype=jnp.float32, kv_heads=None):
    rng = np.random.default_rng(seed)
    mk = lambda h=H: jnp.asarray(  # noqa: E731
        rng.normal(size=(B, T, h, D)), dtype)
    return mk(), mk(kv_heads or H), mk(kv_heads or H)


#: what a backward of each form runs, by the gauges' `kernel`
FORMS = {"fused": ("fwd", "bwd_fused"), "pair": ("fwd", "bwd_dkv", "bwd_dq")}


@pytest.fixture
def form(request, monkeypatch):
    """The form a backward takes, held the way the rule decides it: the
    pair is what a shape gets whose dQ column fits no geometry, so a cap
    of nothing leaves every shape the two kernels.  The traced calls of
    the other form are dropped on the way in and on the way out."""
    if request.param == "pair":
        monkeypatch.setattr(attention, "_FUSED_VMEM_CAP", 0)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


both_forms = pytest.mark.parametrize("form", list(FORMS), indirect=True)


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
#: and (at block-multiple T) not.  And what the lanes rule adds (the
#: last column is the key/value heads, `None` for as many as H): two
#: heads of 64 a 128-lane column block out of two and out of sixteen,
#: an odd head count and a narrow model (the whole width a step), heads
#: of 128 one and two a step, fewer key/value heads than query heads.
DERIVED_CASES = [
    # B, T, H, D, dtype, causal, key/value heads
    (2, 100, 2, 64, jnp.float32, True, None),
    (2, 500, 2, 64, jnp.float32, True, None),
    (2, 256, 4, 64, jnp.float32, True, None),
    (2, 256, 4, 64, jnp.float32, False, None),
    (1, 256, 2, 128, jnp.bfloat16, True, None),
    (1, 500, 2, 128, jnp.bfloat16, True, None),
    (1, 1024, 2, 64, jnp.float32, True, None),
    (1, 1024, 2, 64, jnp.float32, False, None),
    (1, 1024, 2, 128, jnp.float32, True, None),
    (1, 1024, 2, 64, jnp.bfloat16, True, None),
    (1, 256, 16, 64, jnp.float32, True, None),
    (1, 256, 16, 64, jnp.bfloat16, True, None),
    (2, 256, 3, 64, jnp.float32, True, None),
    (1, 300, 3, 64, jnp.float32, True, None),
    (2, 256, 4, 8, jnp.float32, True, None),
    (2, 200, 4, 8, jnp.bfloat16, True, None),
    (1, 256, 4, 128, jnp.float32, False, None),
    (1, 256, 8, 64, jnp.float32, True, 2),
    (1, 300, 4, 64, jnp.bfloat16, True, 1),
]


@pytest.mark.parametrize("B,T,H,D,dtype,causal,kv_heads", DERIVED_CASES)
def test_flash_attention_derived_tiles_match_reference(B, T, H, D, dtype,
                                                       causal, kv_heads):
    """Forward and all three gradients with tiles the rule derives."""
    q, k, v = _qkv(B, T, H, D, dtype=dtype, kv_heads=kv_heads)
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


@both_forms
def test_flash_grad_transposes_nothing_and_copies_no_operand(form):
    """The kernels take q, k, v, dO and give out, dq, dk, dv where the
    projections leave and take them: at `sf-train-backlog`'s shape the
    gradient's jaxpr holds no transpose of a rank-4 array (the folds
    were ten of them), each operand reaches its kernel uncopied, and a
    step takes 128 lanes or more — in the one backward kernel and in
    the two."""
    from iotml.obs.metrics import default_registry

    kernels = FORMS[form]   # the geometry is recorded when a shape is traced
    qkv = [jax.ShapeDtypeStruct((4, 1024, 16, 64), jnp.float32)] * 3
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2)))(*qkv)

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if eqn.primitive.name != "pallas_call":
                    yield from equations(sub)

    names = [e.primitive.name for e in equations(jaxpr.jaxpr)]
    assert names.count("pallas_call") == len(kernels)
    assert not [e for e in equations(jaxpr.jaxpr)
                if e.primitive.name == "transpose"
                and e.invars[0].aval.ndim >= 4]
    assert not {"pad", "concatenate", "gather"} & set(names)
    got = default_registry.collect()
    assert got["iotml_flash_backward_fused"] == (form == "fused")
    for kernel in kernels:
        assert got[f'iotml_flash_operand_copies{{kernel="{kernel}"}}'] == 0
        lanes = got[f'iotml_flash_lanes_per_step{{kernel="{kernel}"}}']
        heads = got[f'iotml_flash_heads_per_step{{kernel="{kernel}"}}']
        assert lanes == heads * 64 and lanes % 128 == 0
        assert got[
            f'iotml_flash_value_lanes_per_step{{kernel="{kernel}"}}'] == lanes
    # a T pad and a repeated k, v are copies, and are counted
    q, k, v = _qkv(1, 300, 4, 64, kv_heads=2)
    jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    got = default_registry.collect()
    assert [got[f'iotml_flash_operand_copies{{kernel="{kernel}"}}']
            for kernel in kernels] == [3, 6, 6][:len(kernels)]


def test_non_causal_flash_attention_still_needs_whole_tiles():
    q, k, v = _qkv(1, 100, 2, 64)
    with pytest.raises(ValueError, match="non-causal"):
        flash_attention(q, k, v, causal=False, interpret=True)


RULE_SHAPES = [
    # T, D, itemsize, B, H, causal
    (100, 64, 4, 2, 2, True), (500, 64, 4, 2, 2, True),
    (256, 64, 4, 16, 16, True), (256, 64, 4, 2, 3, True),
    (1024, 64, 4, 4, 16, True), (1024, 64, 4, 4, 16, False),
    (1024, 128, 2, 1, 2, True), (1152, 64, 4, 2, 4, True),
    (4096, 128, 2, 1, 32, True), (65536, 128, 2, 1, 2, True),
    (65536, 64, 4, 2, 2, True), (1_000_000, 128, 2, 1, 2, True),
    (4096, 64, 4, 1, 32, True), (256, 8, 4, 2, 4, True),
]


@pytest.mark.parametrize("kernel", attention.KERNELS)
@pytest.mark.parametrize("T,D,itemsize,B,H,causal", RULE_SHAPES)
def test_flash_geometry_invariants(kernel, T, D, itemsize, B, H, causal):
    g = attention.flash_geometry(kernel, T, D, itemsize, B, H, causal)
    bh = B * H
    t_pad = -(-T // 128) * 128
    if kernel == "bwd_fused":
        # the one kernel wherever some geometry holds the dQ column,
        # twice, beside its blocks: the smallest's count says which
        fits = attention._vmem_bytes(
            kernel, 128, 128, attention._head_groups(H, D)[0], D, itemsize,
            t_q=t_pad) <= attention._FUSED_VMEM_CAP
        assert (g is not None) == fits == (T < 65536)
        if g is None:
            return
    for block in (g.block_q, g.block_k):
        assert block % 128 == 0 and t_pad % block == 0
        assert block <= attention._MAX_BLOCK
    assert (g.t_q, g.t_k) == (t_pad, t_pad)
    # a step's heads are whole 128-lane columns of [B, T, H·D], or all
    assert H % g.heads == 0
    assert g.heads * D % 128 == 0 or g.heads == H
    assert g.heads <= attention._MAX_HEADS or g.heads == H
    assert attention._vmem_bytes(kernel, g.block_q, g.block_k, g.heads, D,
                                 itemsize, t_q=g.t_q) \
        <= attention._vmem_cap(kernel)
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
    # the fewest heads the lanes allow: two of 64, one of 128
    g = attention.flash_geometry(kernel, 1024, 64, 4, 4, 16, True, 128, 128)
    assert (g.block_q, g.block_k, g.heads, g.grid_steps) == (
        128, 128, 2, 32 * 36)
    g = attention.flash_geometry(kernel, 1024, 128, 2, 4, 16, True, 128, 128)
    assert (g.heads, g.grid_steps) == (1, 64 * 36)
    # one named, the other derived around it; still the fewest heads
    g = attention.flash_geometry(kernel, 1024, 64, 4, 4, 16, True,
                                 block_k=256)
    assert (g.block_k, g.heads) == (256, 2) and 1024 % g.block_q == 0
    # blocks that are no multiple of 128 pad T to themselves, as before;
    # a model narrower than 128 lanes takes its whole width
    g = attention.flash_geometry(kernel, 40, 8, 4, 2, 2, True, 16, 16)
    assert (g.block_q, g.block_k, g.t_q, g.t_k, g.heads) == (
        16, 16, 48, 48, 2)
    # the backward's cap sits in the rule: the forward takes 2048 as
    # named, the backward kernels stop at the largest tile that compiles
    g = attention.flash_geometry(kernel, 65536, 128, 2, 1, 2, True,
                                 2048, 2048)
    if kernel == "bwd_fused":
        # no block makes a 32 MiB column smaller: the two kernels
        assert g is None
        return
    want = 2048 if kernel == "fwd" else attention._MAX_BLOCK
    assert (g.block_q, g.block_k) == (want, want)


@pytest.mark.parametrize("T", [100, 128, 200, 256, 300])
@pytest.mark.parametrize("H,D,Dv", [(4, 48, 32),     # both under 128 lanes
                                    (2, 192, 128)])  # the latent's heads
def test_flash_attention_takes_value_heads_of_another_width(T, H, D, Dv):
    """Latent attention's training form: query and key heads carry the
    rotary features, value heads do not (192 beside 128 at the published
    widths).  The kernels (interpreted) against the reference — out, dq,
    dk, dv — over T under, at and over a block of 128, the padded
    lengths included."""
    rng = np.random.default_rng(T + D)
    mk = lambda d: jnp.asarray(  # noqa: E731
        rng.normal(size=(2, T, H, d)), jnp.float32)
    q, k, v, w = mk(D), mk(D), mk(Dv), mk(Dv)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(w * fn(q, k, v)), argnums=(0, 1, 2))(
                q, k, v)

    out = flash_attention(q, k, v, True, 128, 128, True)
    assert out.shape == (2, T, H, Dv)
    np.testing.assert_allclose(out, attention_reference(q, k, v, True),
                               rtol=2e-5, atol=2e-5)
    (got, grads), (want, wants) = both(
        lambda q, k, v: flash_attention(q, k, v, True, 128, 128, True)), \
        both(lambda q, k, v: attention_reference(q, k, v, True))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for g, r, like in zip(grads, wants, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def test_flash_geometry_by_both_widths():
    """At the latent's shape a step takes two heads — 384 lanes of q and
    k beside 256 of v and out — and the counted VMEM knows both widths;
    equal widths derive what they derived before the second width
    existed, and say so (`iotml_flash_value_lanes_per_step`)."""
    from iotml.obs.metrics import default_registry

    for kernel in attention.KERNELS:
        geom = attention.flash_geometry(kernel, 8192, 192, 4, 1, 16, True,
                                        Dv=128)
        assert geom.heads * 192 % 128 == 0 and geom.heads * 128 % 128 == 0
        narrow, wide = (attention._vmem_bytes(
            kernel, geom.block_q, geom.block_k, geom.heads, 192, 4, dv,
            geom.t_q) for dv in (128, 192))
        assert narrow < wide == attention._vmem_bytes(
            kernel, geom.block_q, geom.block_k, geom.heads, 192, 4,
            t_q=geom.t_q)
        assert narrow <= attention._vmem_cap(kernel)
        for shape in ((1024, 64, 4, 4, 16, True), (4096, 64, 4, 1, 32, True)):
            assert attention.flash_geometry(kernel, *shape) \
                == attention.flash_geometry(kernel, *shape, Dv=shape[1])
    assert attention._head_groups(16, 192, 128) == [2, 4, 8]
    assert attention._head_groups(3, 192, 128) == [3]   # the whole width
    jax.clear_caches()
    q, k, v = _qkv(1, 256, 2, 192)
    jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v[..., :128], interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    got = default_registry.collect()
    # the column is q's width: two heads of 192, 256 rows, float32
    assert got["iotml_flash_bwd_column_bytes"] == 256 * 384 * 4
    for kernel in FORMS["fused"]:
        assert got[f'iotml_flash_lanes_per_step{{kernel="{kernel}"}}'] == 384
        assert got[
            f'iotml_flash_value_lanes_per_step{{kernel="{kernel}"}}'] == 256
        # nothing padded, nothing repeated: v narrower than q costs no copy
        assert got[f'iotml_flash_operand_copies{{kernel="{kernel}"}}'] == 0


# ------------------------------------------------------ a sliding window
def _live_tiles_by_hand(nq, nk, bq, bk, window, order):
    """Every tile that holds a score the mask lets through, in the order
    a grid walks them."""
    live = []
    for i in range(nq):
        for j in range(nk):
            t = np.arange(i * bq, (i + 1) * bq)[:, None]
            k = np.arange(j * bk, (j + 1) * bk)[None, :]
            mask = k <= t
            if window is not None:
                mask &= k > t - window
            if mask.any():
                live.append((i, j))
    return sorted(live, key=lambda ij: ij[::-1]) if order == "col" else live


@pytest.mark.parametrize("order", ["row", "col"])
@pytest.mark.parametrize("T,bq,bk,window", [
    (1024, 128, 128, 200),    # a window that is no multiple of a block
    (1024, 256, 128, 100),    # … and narrower than either block
    (1024, 128, 256, 300),
    (700, 128, 128, 64),      # T pads
    (1100, 1024, 128, 64),    # the q side pads further than the kv side
    (2048, 512, 256, None),   # no window: the triangle, as it was
])
def test_band_tile_maps_are_the_brute_force_enumeration(T, bq, bk, window,
                                                        order):
    nq, nk = -(-T // bq), -(-T // bk)
    im, jm = attention._causal_tiles(nq, nk, bq, bk, order, window)
    want = _live_tiles_by_hand(nq, nk, bq, bk, window, order)
    assert list(zip(im.tolist(), jm.tolist())) == want
    assert attention._tri_tile_count(nq, nk, bq, bk, window, order) \
        == len(want)
    if window is None:
        # the triangle's count is the one the geometry always used
        assert attention._tri_tile_count(nq, nk, bq, bk) == len(want)


@pytest.mark.parametrize("T,window,block,H,kv_heads", [
    (300, 50, 128, 4, 2),      # a window narrower than a block
    (300, 200, 128, 4, 2),     # … that is no multiple of one
    (300, 130, None, 4, 2),    # derived tiles
    (300, 1, 128, 4, 2),       # the position itself, alone
    (200, 72, 128, 7, 1),      # seven query heads on one key/value head
])
def test_band_flash_attention_matches_the_reference(T, window, block, H,
                                                    kv_heads):
    """Forward and all three gradients of the band call, interpreted,
    against `attention_reference(window=)`."""
    q, k, v = _qkv(B=1, T=T, H=H, D=32, seed=T + window, kv_heads=kv_heads)
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, block, block, True, window=window)
    plain = lambda q, k, v: attention_reference(  # noqa: E731
        q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=2e-5)
    # key 0 moves the queries that still meet it (but query 0, which
    # meets nothing else), and none the window has carried past it
    moved = plain(q, k.at[:, 0].add(1.0), v) - plain(q, k, v)
    reach = np.abs(np.asarray(moved)).max(axis=(0, 2, 3)) > 0
    assert reach[1:window].all() and not reach[window:].any()


@pytest.mark.parametrize("kernel", attention.KERNELS)
def test_a_window_of_the_whole_length_is_the_causal_call(kernel):
    """`window ≥ T`, or none: the geometry the causal call always had,
    field by field, and the same traced call — one cache entry for
    both."""
    args = (kernel, 4096, 128, 4, 2, 8, True)
    causal = attention.flash_geometry(*args)
    assert causal.window is None and causal.tri
    for window in (4096, 5000):
        assert attention.flash_geometry(*args, window=window) == causal
    band = attention.flash_geometry(*args, window=1024)
    area = lambda g: g.tiles * g.block_q * g.block_k  # noqa: E731
    assert band.window == 1024 and area(band) < area(causal)
    q, k, v = _qkv(B=1, T=160, H=2, D=32)
    jax.clear_caches()
    want = flash_attention(q, k, v, True, 128, 128, True)
    traced = attention._flash_forward._cache_size()
    got = flash_attention(q, k, v, True, 128, 128, True, window=160)
    assert attention._flash_forward._cache_size() == traced
    assert np.array_equal(got, want)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, True, 128, 128, True, window=0)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, False, 32, 32, True, window=8)


def test_a_band_past_the_cap_on_the_maps_walks_the_dense_grid(monkeypatch):
    """More live tiles than the scalar-prefetch maps may hold: the dense
    grid, whose `pl.when` skips the tiles behind the band as it skips
    the future's."""
    monkeypatch.setattr(attention, "_TRI_TILE_CAP", 2)
    jax.clear_caches()
    q, k, v = _qkv(B=1, T=500, H=2, D=32, seed=9)
    geom = attention.flash_geometry("fwd", 500, 32, 4, 1, 2, True, 128, 128,
                                    window=100)
    assert not geom.tri and geom.window == 100
    f = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(  # noqa: E731
        q, k, v, True, 128, 128, True, window=100)))
    r = lambda q, k, v: jnp.sum(jnp.sin(attention_reference(  # noqa: E731
        q, k, v, causal=True, window=100)))
    for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                    jax.grad(r, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=2e-5)
    jax.clear_caches()


@both_forms
def test_the_gauges_tell_a_band_call_from_a_causal_one(form):
    """By kernel and mask: a causal call and a band call of one program
    both stand; the keys by kernel alone are the last traced call's."""
    from iotml.obs.metrics import default_registry

    q, k, v = _qkv(B=1, T=300, H=2, D=64)
    for window in (None, 100):
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, True, 128, 128, True, window=window)))(q)
    said = default_registry.collect()
    for kernel in FORMS[form]:
        by = lambda what, mask: said[  # noqa: E731
            f'iotml_flash_mask_{what}{{kernel="{kernel}",kind="{mask}"}}']
        assert (by("window", "causal"), by("window", "band")) == (0, 100)
        assert (by("tiles", "causal"), by("tiles", "band")) == (6, 5)
        assert by("walked_area", "band") == 5 * 128 * 128
        assert by("live_area", "causal") == 300 * 301 // 2
        assert by("live_area", "band") == attention.mask_area(300, True, 100) \
            == 100 * 101 // 2 + 200 * 100
        # the band's call was the last traced
        assert said[f'iotml_flash_grid_steps{{kernel="{kernel}"}}'] == 5 * 2 \
            / said[f'iotml_flash_heads_per_step{{kernel="{kernel}"}}']
    assert attention.mask_area(300, False) == 300 * 300


# ------------------------------------------- the one-kernel backward
#: B, T, H, D, Dv, dtype, causal, key/value heads, window, blocks
FUSED_CASES = {
    "triangle": (2, 384, 2, 32, 32, jnp.float32, True, None, None, 128),
    # a band whose near edge cuts the diagonal's tiles and whose far
    # edge ends inside a tile two columns back
    "band": (1, 512, 2, 32, 32, jnp.float32, True, None, 200, 128),
    "dense": (1, 256, 2, 32, 32, jnp.float32, False, None, None, 128),
    "padded": (2, 300, 2, 32, 32, jnp.float32, True, None, None, 128),
    "padded_band": (1, 300, 2, 32, 32, jnp.float32, True, None, 130, None),
    "four_heads_of_64": (1, 256, 4, 64, 64, jnp.float32, True, None, None,
                         None),
    "latent_widths": (1, 300, 2, 192, 128, jnp.float32, True, None, None,
                      128),
    "repeated_kv": (1, 300, 4, 64, 64, jnp.float32, True, 2, None, 128),
    "bfloat16": (1, 300, 2, 128, 128, jnp.bfloat16, True, None, None, None),
    "small_blocks": (2, 40, 2, 8, 8, jnp.float32, True, None, None, 16),
}


def _grads(case, fn, seed=0):
    B, T, H, D, Dv, dtype, causal, kv_heads, window, block = FUSED_CASES[case]
    rng = np.random.default_rng(seed)
    mk = lambda h, d: jnp.asarray(  # noqa: E731
        rng.normal(size=(B, T, h, d)), dtype)
    q, k, v = mk(H, D), mk(kv_heads or H, D), mk(kv_heads or H, Dv)
    w = mk(H, Dv).astype(jnp.float32)
    if fn is attention_reference:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        call = lambda q, k, v: fn(  # noqa: E731
            q, k, v, causal=causal, window=window)
    else:
        call = lambda q, k, v: fn(  # noqa: E731
            q, k, v, causal, block, block, True, window=window)
    return jax.grad(lambda *a: jnp.sum(w * call(*a).astype(jnp.float32)),
                    (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_the_fused_backward_gives_the_two_kernels_gradients(case,
                                                            monkeypatch):
    """dq, dk and dv of the one backward kernel — dQ summed into its
    resident float32 column on dK/dV's grid — against
    `attention_reference`'s gradients, to this file's tolerances, AND
    against the two-kernel form's on the same operands, to float32's
    rounding: the same five products on the same operands, the sums
    over kv blocks in the same order."""
    from iotml.obs.metrics import default_registry

    dtype = FUSED_CASES[case][5]
    jax.clear_caches()
    fused = _grads(case, flash_attention)
    said = default_registry.collect()
    assert said["iotml_flash_backward_fused"] == 1
    if case == "four_heads_of_64":
        assert said['iotml_flash_heads_per_step{kernel="bwd_fused"}'] == 4
    monkeypatch.setattr(attention, "_FUSED_VMEM_CAP", 0)
    jax.clear_caches()
    pair = _grads(case, flash_attention)
    assert default_registry.collect()["iotml_flash_backward_fused"] == 0
    jax.clear_caches()
    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    up = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    for one, two, want in zip(fused, pair, _grads(case, attention_reference)):
        assert one.dtype == two.dtype == dtype and one.shape == want.shape
        np.testing.assert_allclose(up(one), up(want), **tol)
        # bfloat16: the float32 column cast once, as the scratch was
        np.testing.assert_allclose(up(one), up(two), rtol=1e-6, atol=1e-6)


def test_the_fused_backward_on_the_dense_causal_grid(monkeypatch):
    """Past the cap on the maps the fused kernel walks the dense grid
    and skips dead tiles by `pl.when`: the column is zeroed at the
    walk's first tile whether or not that tile is live."""
    monkeypatch.setattr(attention, "_TRI_TILE_CAP", 2)
    jax.clear_caches()
    fused = _grads("band", flash_attention)
    assert not attention.flash_geometry(
        "bwd_fused", 512, 32, 4, 1, 2, True, 128, 128, window=200).tri
    for one, want in zip(fused, _grads("band", attention_reference)):
        np.testing.assert_allclose(np.asarray(one), np.asarray(want),
                                   rtol=1e-4, atol=2e-5)
    jax.clear_caches()


#: the attention call of every listed cell, (B, T, H, D, Dv, window),
#: float32: `flash_attention`'s operands after the repeat of k and v
CELL_SHAPES = {
    "sf": (4, 1024, 16, 64, 64, None),
    "gh": (1, 4096, 32, 64, 64, None),
    "km": (1, 8192, 16, 192, 128, None),
    "ns": (1, 8192, 4, 128, 128, None),
    "lf": (2, 8192, 32, 64, 64, None),
    "ou": (1, 8192, 16, 128, 128, None),
    "st": (2, 16384, 28, 128, 128, None),
    "st_band": (2, 16384, 28, 128, 128, 4096),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_every_listed_cells_backward_takes_the_one_kernel(cell):
    """Which form a backward takes is read off the shape: at every
    listed cell's shape some geometry holds the dQ column twice beside
    its blocks under the fused call's cap — on dK/dV's grid, the band's
    tiles in column order — and the limit the call states is its own
    count and the margin."""
    B, T, H, D, Dv, window = CELL_SHAPES[cell]
    g = attention.flash_geometry("bwd_fused", T, D, 4, B, H, True, Dv=Dv,
                                 window=window)
    assert g is not None and g.tri and g.window == window
    counted = attention._vmem_bytes("bwd_fused", g.block_q, g.block_k,
                                    g.heads, D, 4, Dv, g.t_q)
    column = attention._column_bytes(g.t_q, g.heads, D)
    assert column == T * g.heads * D * 4
    assert 2 * column < counted <= attention._FUSED_VMEM_CAP
    assert counted + attention._VMEM_MARGIN <= 96 * 2 ** 20
    dkv = attention._geometry(T, B * H, True, g.block_q, g.block_k, g.heads,
                              window, kv_outer=True)
    assert g == dkv


@pytest.mark.parametrize("shape", [
    (1, 65536, 2, 128, 2),       # the long context: 32 MiB, twice
    (2, 65536, 2, 64, 4),
    (1, 1_000_000, 2, 128, 2),
])
def test_a_column_that_fits_no_geometry_takes_the_two_kernels(shape):
    B, T, H, D, itemsize = shape
    assert attention.flash_geometry("bwd_fused", T, D, itemsize, B, H,
                                    True) is None
    for kernel in ("bwd_dkv", "bwd_dq"):
        assert attention.flash_geometry(kernel, T, D, itemsize, B, H, True)


@both_forms
def test_the_gauges_say_which_backward_ran(form):
    """`iotml_flash_backward_fused`, the `kernel="bwd_fused"` series and
    the column's bytes after a traced backward of each form; the form
    that did not run sets nothing new."""
    from iotml.obs.metrics import default_registry

    before = default_registry.collect()
    q, k, v = _qkv(B=1, T=300, H=2, D=64)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, True, 128, 128, True, window=100)))(q)
    said = default_registry.collect()
    other = [kern for kern in attention.KERNELS if kern not in FORMS[form]]
    for key in said:
        if any(f'kernel="{kern}"' in key for kern in other):
            assert said[key] == before.get(key), key
    if form == "pair":
        assert (said["iotml_flash_backward_fused"],
                said["iotml_flash_bwd_column_bytes"]) == (0, 0)
        return
    assert said["iotml_flash_backward_fused"] == 1
    # T pads to 384 rows; two heads of 64 a step: 128 lanes of float32
    assert said["iotml_flash_bwd_column_bytes"] == 384 * 128 * 4
    by = lambda name: said[  # noqa: E731
        f'iotml_flash_{name}{{kernel="bwd_fused"}}']
    assert (by("block_q"), by("block_k"), by("heads_per_step"),
            by("lanes_per_step"), by("value_lanes_per_step")) \
        == (128, 128, 2, 128, 128)
    # the band's live tiles in column order, one step for both heads
    assert by("grid_steps") == 5
    assert by("operand_copies") == 6
    mask = lambda name: said[  # noqa: E731
        f'iotml_flash_mask_{name}{{kernel="bwd_fused",kind="band"}}']
    assert (mask("window"), mask("tiles"), mask("walked_area"),
            mask("live_area")) == (100, 5, 5 * 128 * 128,
                                   attention.mask_area(300, True, 100))
