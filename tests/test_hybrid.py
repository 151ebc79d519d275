"""The hybrid stack (Mamba-2 state-space mixers beside grouped-query
attention): the chunked scan against the step-by-step recurrence of the
benchmark's plain reference (`stacks.reference`), grouped heads and an
explicit scale through the flash kernels, the convolution kernels
(interpreted) against the plain form, and what the trace-time counters
say.  The model and one compiled job against the reference are the
`granite` cases of `test_stack_contract.py`.  All at a tiny preset on
the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models.hybrid import HybridConfig, SensorHybrid
from iotml.ops.attention import attention_reference, flash_attention
from iotml.ops import ssd
from iotml.ops.ssd import (causal_conv1d, causal_conv1d_fused,
                           causal_conv1d_silu, ssd_scan)
from stacks import close as _close


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("granite")[0]


def _scan_inputs(B, T, H=4, P=16, N=8, seed=0, dt_scale=1.0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return (f32(B, T, H, P), dt_scale * jax.nn.softplus(f32(B, T, H)),
            -jnp.exp(f32(H)), f32(B, T, N), f32(B, T, N))


# ------------------------------------------------------------- the scan
@pytest.mark.parametrize("T,B,chunk", [
    (32, 2, 8),     # a multiple of the chunk
    (32, 1, 16),
    (21, 2, 8),     # not a multiple: padded with Δ = 0
    (43, 1, 16),
    (5, 2, 8),      # shorter than one chunk
    (8, 3, 8),      # exactly one chunk
])
def test_chunked_scan_matches_the_recurrence(ref, T, B, chunk):
    args = _scan_inputs(B, T, seed=T)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        # one compilation a side: the value and all five gradients
        got, want = (jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(w * f(*a)), argnums=range(5)))(*args)
            for f in (lambda *a: ssd_scan(*a, chunk), ref._recurrence))
        _close(jax.jit(lambda *a: ssd_scan(*a, chunk))(*args),
               jax.jit(ref._recurrence)(*args))
    _close(got, want)


def test_chunked_scan_takes_decays_a_naive_exp_would_overflow(ref):
    """Δ·|a| sums to thousands within a chunk: exp(-cum_s) alone is
    inf in float32, the masked difference never leaves [0, 1]."""
    x, dt, a, b, c = _scan_inputs(1, 64, seed=3, dt_scale=20.0)
    a = a - 5.0
    assert float(jnp.sum(dt * -a, axis=1).max()) > 1000.0
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(x, dt, a, b, c, 32)
        want = jax.jit(ref._recurrence)(x, dt, a, b, c)
        g = jax.jit(jax.grad(lambda *v: jnp.sum(ssd_scan(*v, 32)),
                             argnums=range(5)))(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(v).all()) for v in (got, *g))
    _close(got, want)


@pytest.fixture
def short_blocks(monkeypatch):
    """Blocks of one lane tile (128 positions), so that a few hundred
    positions are several blocks: the tile ahead of a block and the
    backward's carry from block to block are in play."""
    monkeypatch.setattr(ssd, "_CONV_MAX_T", 128)


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_causal_conv_matches_the_grouped_convolution(form, short_blocks):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 141, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 24)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    want = jax.lax.conv_general_dilated(
        x, w[:, None, :], (1,), [(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=24) + bias
    conv = causal_conv1d
    if form == "kernel":   # the activation inside, two blocks of 128
        want = jax.nn.silu(want)
        conv = lambda *a: causal_conv1d_silu(*a)[0]  # noqa: E731
    _close(conv(x, w, bias), want, rtol=1e-6)
    # causal: a later input moves no earlier output
    moved = conv(x.at[:, 129:].set(0.0), w, bias)
    np.testing.assert_array_equal(np.asarray(moved[:, :129]),
                                  np.asarray(conv(x, w, bias)[:, :129]))


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("K,activation", [
    (1, "silu"), (4, "silu"),     # Mamba-2's: four taps under a SiLU
    (3, "none")])                 # the gated short convolution's: three
#                                   taps and the sum as it stands
@pytest.mark.parametrize("T", [5, 256, 200])   # under a block of 128, a
@pytest.mark.parametrize("C", [80, 256, 384])  # multiple, a ragged tail
def test_conv_kernels_match_the_plain_form(C, T, K, activation, B,
                                           short_blocks):
    """Output, dx, dkernel and dbias of `causal_conv1d_fused` against
    act(causal_conv1d) and its `jax.grad`, in three runs (the second
    and the third read from a later row block of x), under either
    activation; what the kernels say they applied; and which operands
    the wrapper had to copy."""
    from iotml.obs.metrics import default_registry

    rng = np.random.default_rng(C + T + K + B)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, kernel, bias = f32(B, T, C), f32(K, C), f32(C)
    n = C // 3 // 4 * 4
    splits = (C - 2 * n, n, n)   # 80: 32 + 24 + 24; 256: 88 + 84 + 84
    weights = tuple(f32(B, T, w) for w in splits)

    def plain(x, kernel, bias):
        y = causal_conv1d(x, kernel, bias)
        if activation == "silu":
            y = jax.nn.silu(y)
        return tuple(jnp.split(y, [splits[0], splits[0] + n], axis=-1))

    def kernels(x, kernel, bias):
        return causal_conv1d_fused(x, kernel, bias, splits=splits,
                                   activation=activation)

    got, want = (jax.value_and_grad(
        lambda *a: sum(jnp.sum(w * y) for w, y in zip(weights, f(*a))),
        argnums=(0, 1, 2))(x, kernel, bias) for f in (kernels, plain))
    _close(kernels(x, kernel, bias), plain(x, kernel, bias), rtol=2e-6)
    _close(got, want, rtol=2e-5)
    # in place: a run of whole sublane tiles that starts on one (all of
    # 80's and 384's, the first of 256's: 84 channels are no whole
    # tiles), its positions whole lane tiles (256 alone); else x is
    # sliced out and padded, and dy with it
    said = default_registry.collect()
    copied = {80: 0, 256: 2, 384: 0}[C] if T == 256 else 3
    assert said['iotml_conv_operand_copies{kernel="fwd"}'] == copied
    assert said['iotml_conv_operand_copies{kernel="bwd"}'] == 2 * copied
    assert said["iotml_conv_block_t"] == 128
    assert said["iotml_conv_block_c"] == {80: 32, 256: 88, 384: 128}[C]
    assert said["iotml_conv_taps"] == K
    assert said["iotml_conv_activation_fused"] == (activation == "silu")


def test_conv_without_a_bias_is_the_conv_with_a_zero_one():
    """`bias` None: zeros to the same kernels, no second path — and the
    SiLU's entry point is the fused one's default."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 130, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
    zero = jnp.zeros((16,), jnp.float32)
    for activation in ("none", "silu"):
        np.testing.assert_array_equal(
            causal_conv1d_fused(x, w, activation=activation)[0],
            causal_conv1d_fused(x, w, zero, activation=activation)[0])
    np.testing.assert_array_equal(causal_conv1d_silu(x, w, zero)[0],
                                  causal_conv1d_fused(x, w, zero)[0])
    _close(causal_conv1d_fused(x, w, activation="none")[0],
           causal_conv1d(x, w, zero), rtol=1e-6)


def test_conv_kernels_refuse_what_they_cannot_place():
    x, bias = jnp.zeros((1, 16, 24)), jnp.zeros((24,))
    with pytest.raises(ValueError, match="are not the 24"):
        causal_conv1d_silu(x, jnp.zeros((4, 24)), bias, splits=(16, 16))
    with pytest.raises(ValueError, match="are not the 24"):
        causal_conv1d_silu(jnp.zeros((1, 16, 32)), jnp.zeros((4, 24)), bias)
    with pytest.raises(ValueError, match="reach past"):
        causal_conv1d_silu(x, jnp.zeros((130, 24)), bias)
    with pytest.raises(ValueError, match="is none of"):
        causal_conv1d_fused(x, jnp.zeros((4, 24)), bias, activation="gelu")


# ------------------------------------------------------------ the model
def test_layer_types_are_data_of_the_model():
    x = stacks.batch(T=21)[0]
    for kinds in (("attention",), ("mamba", "mamba"),
                  ("mamba", "attention", "mamba", "attention")):
        model = SensorHybrid(HybridConfig(layer_types=kinds))
        params = jax.jit(model.init)(jax.random.PRNGKey(0), x)["params"]
        for i, kind in enumerate(kinds):
            assert ("A_log" in params[f"layer{i}"]["mixer"]) \
                == (kind == "mamba")
        assert jax.jit(model.apply)({"params": params}, x).shape == x.shape
    with pytest.raises(ValueError, match="known kinds"):
        SensorHybrid(HybridConfig(layer_types=("mamba", "lstm"))).init(
            jax.random.PRNGKey(0), x)


def test_a_tiny_fit_says_what_engaged():
    """The trace-time counters after a fit: the scan's chunking, the
    state a sequence holds, the convolution kernels' blocks, the layers
    by kind, the recomputed blocks."""
    from iotml.obs.metrics import default_registry
    from iotml.train.loop import Trainer

    cfg = HybridConfig(layer_types=("mamba", "attention", "mamba", "mamba"))
    Trainer(SensorHybrid(cfg), supervised=True).fit_compiled(
        stacks.jobs([stacks.batch(T=21)]), epochs=1)
    got = default_registry.collect()
    assert got["iotml_ssd_chunk_size"] == cfg.chunk
    assert got["iotml_ssd_chunks"] == 3          # 21 positions in eights
    assert got["iotml_ssd_state_bytes"] == \
        cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    # the convolution: runs of 64, 8 and 8 channels of the sliced
    # stream are whole sublane tiles, but 21 positions fill no lane
    # tile, so every run is padded (x, and dy in the backward) to 128
    for kernel, copies in (("fwd", 3), ("bwd", 6)):
        assert got[f'iotml_conv_grid_steps{{kernel="{kernel}"}}'] == 6
        assert got[f'iotml_conv_operand_copies{{kernel="{kernel}"}}'] \
            == copies
    assert got["iotml_conv_block_t"] == 128
    assert got["iotml_conv_block_c"] == 64
    assert got['iotml_model_layers{kind="mamba"}'] == 3
    assert got['iotml_model_layers{kind="attention"}'] == 1
    assert got["iotml_remat_blocks"] == 4
    # every layer's MLP keeps its first product [42, 2 x 128] here
    assert got['iotml_remat_kept_bytes{kind="ffn"}'] == 4 * 42 * 256 * 4
    assert got['iotml_remat_kept_layers{kind="ffn"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn"}'] == 4
    # `dense` attention ran no kernel, and the stack has no other part
    # whose values are kept
    stacks.only_these_kinds_are_kept(got, "ffn")


# ----------------------------- grouped heads and a scale through the kernels
def _gqa(B=2, T=40, H=4, G=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.normal(size=(B, T, h, D)),  # noqa: E731
                               jnp.float32)
    return mk(H), mk(G), mk(G)


@pytest.mark.parametrize("H,G,scale", [(4, 2, 0.015625), (4, 1, None),
                                       (8, 2, 0.3), (2, 2, 0.05)])
def test_flash_grouped_heads_and_scale_match_the_reference(H, G, scale):
    q, k, v = _gqa(H=H, G=G, seed=H + G)
    rep = lambda t: jnp.repeat(t, H // G, axis=2)  # noqa: E731
    w = jnp.asarray(np.random.default_rng(2).normal(size=q.shape),
                    jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True, scale=scale)

    def plain(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) \
            * (1 / np.sqrt(d) if scale is None else scale)
        mask = jnp.arange(q.shape[1])[:, None] >= jnp.arange(q.shape[1])
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, rep(v))

    want = plain(q, k, v)
    np.testing.assert_allclose(flash(q, k, v), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        attention_reference(q, k, v, causal=True, scale=scale), want,
        rtol=2e-5, atol=2e-5)
    got_g, want_g = (jax.jit(jax.grad(lambda *a: jnp.sum(w * f(*a)),
                                      argnums=(0, 1, 2)))(q, k, v)
                     for f in (flash, plain))
    for a, b in zip(got_g, want_g):
        assert a.shape == b.shape   # dk, dv summed back over the group
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_flash_default_path_is_bit_for_bit_unchanged():
    """Equal heads and no scale: the same kernels on the same operands
    as a call that names 1/sqrt(D), forward and backward."""
    q, k, v = _gqa(H=2, G=2, T=40)
    plain = lambda *a: flash_attention(  # noqa: E731
        *a, True, 16, 16, True)
    named = lambda *a: flash_attention(  # noqa: E731
        *a, causal=True, block_q=16, block_k=16, interpret=True,
        scale=1.0 / np.sqrt(16))
    np.testing.assert_array_equal(plain(q, k, v), named(q, k, v))
    for a, b in zip(*(jax.grad(lambda *t: jnp.sum(jnp.sin(f(*t))),
                               argnums=(0, 1, 2))(q, k, v)
                      for f in (plain, named))):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="do not group"):
        flash_attention(*_gqa(H=4, G=3), interpret=True)
