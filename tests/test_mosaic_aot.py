"""The Pallas kernels must LOWER for the real chip, checked without one.

libtpu ships a compile-only TPU topology: `jax.jit(...).lower(...)
.compile()` against one of its devices runs the whole Mosaic pipeline
with `interpret=False` on a box that has no accelerator.  The rest of
the suite runs the kernels under the Pallas interpreter, which accepts
programs Mosaic refuses (unaligned dynamic stores, 1-D iota, powf …) —
so without this a PR that breaks lowering is only found on chip budget.
Compilation only: whether the kernels run and agree on the device is
`chip_smoke.py`'s job.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from iotml.ops import attention, fused_train, rope
from iotml.ops.attention import flash_attention
from iotml.ops.moe import rotary
from iotml.ops.ssd import causal_conv1d_fused


@pytest.fixture(scope="module")
def v5e():
    """Sharding onto one device of a compile-only v5e topology."""
    from jax.experimental import topologies

    # off a cloud VM the metadata-server query only stalls topology setup
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 - any libtpu refusal is a skip
        pytest.skip(f"libtpu offers no compile-only v5e topology: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("S,B,E", [(100, 100, 20),   # the reference job
                                   (200, 100, 1)])   # a live e2e round
def test_fused_fit_lowers_for_v5e(v5e, S, B, E):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    # CAR_AUTOENCODER 18->14->7->7->18: kernel, bias per layer
    flat = [sds(s) for s in ((18, 14), (14,), (14, 7), (7,),
                             (7, 7), (7,), (7, 18), (18,))]
    fused_train._fused_fit.lower(
        flat, flat, flat, sds((), jnp.int32), sds((S, B, 18)), sds((S, B)),
        epochs=E, lr=1e-3, l1=1e-7, b1=0.9, b2=0.999, eps=1e-8,
        interpret=False).compile()


def test_flash_attention_fwd_bwd_lowers_for_v5e(v5e):
    # T=500 is not a multiple of the 128² blocks: the padded path lowers too
    qkv = [jax.ShapeDtypeStruct((2, 500, 4, 64), jnp.float32, sharding=v5e)
           for _ in range(3)]

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 128, 128, False))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv).compile()


@pytest.mark.parametrize("shape,dtype", [
    ((4, 1024, 16, 64), jnp.float32),     # `sf-train-backlog`, exactly
    ((16, 256, 16, 64), jnp.float32),     # the short window
    ((1, 65536, 2, 128), jnp.bfloat16),   # the long context
    ((2, 512, 3, 64), jnp.float32),       # odd heads: all 192 lanes a step
    ((2, 512, 4, 8), jnp.float32),        # a model 32 lanes wide
    ((2, 300, 6, 64), jnp.bfloat16),      # padded T, 16-row sublane tiles
])
def test_flash_attention_derived_tiles_lower_for_v5e(v5e, shape, dtype):
    """Tiles and heads a step left to the rule, forward and backward: a
    geometry that overflows scoped VMEM, or a lane slice of a
    [1, block, G·D] column block that Mosaic refuses, fails here and
    not on the chip."""
    qkv = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
           for _ in range(3)]

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv).compile()


@pytest.mark.parametrize("T,H,G,window", [
    (16384, 28, 4, 4096),    # `st-train-backlog`'s window layers, exactly
    (1024, 4, 2, 100),       # a window that ends inside a tile
])
def test_flash_attention_under_a_window_lowers_for_v5e(v5e, T, H, G, window):
    """Forward and backward under a sliding window — the band's
    scalar-prefetch maps and the far edge's mask through Mosaic: 28
    query heads over 4 key/value heads of 128 at T = 16,384 and the
    source's window of 4,096, and a small window inside one tile."""
    q = jax.ShapeDtypeStruct((2, T, H, 128), jnp.float32, sharding=v5e)
    kv = jax.ShapeDtypeStruct((2, T, G, 128), jnp.float32, sharding=v5e)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()


def test_flash_attention_grouped_heads_lower_for_v5e(v5e):
    """`gh-train-backlog`'s one attention layer, exactly, forward and
    backward: 32 query heads over 8 key/value heads of 64 at T = 4,096,
    the scale the source names."""
    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.float32, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.float32, sharding=v5e)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=0.015625))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()


def test_flash_attention_latent_heads_lower_for_v5e(v5e):
    """`km-train-backlog`'s attention, exactly, forward and backward:
    16 heads of 192 query/key features beside 128 value features at
    T = 8,192 in float32 — column blocks of 384 and 256 lanes a step."""
    qk = jax.ShapeDtypeStruct((1, 8192, 16, 192), jnp.float32, sharding=v5e)
    v = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.float32, sharding=v5e)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    # the forward and the one backward kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert attention.BWD_FUSED_KERNEL in text


@pytest.mark.parametrize("form,calls", [
    ("pairs", 2),         # XLA's pair form: the two flash kernels
    ("lanes", 2 + 4),     # and q's and k's turn, and their cotangents'
])
def test_flash_attention_turned_grouped_heads_lower_for_v5e(v5e, form, calls):
    """`lf-train-backlog`'s one attention layer, exactly, forward and
    backward: two windows of 8,192 positions, 32 query heads over 8
    key/value heads of 64 (`gh-train-backlog`'s head shape at twice the
    window), queries and keys turned by rotary positions over the whole
    head ahead of the kernels — by `iotml_rope` on `[B, T, H·D]`, as the
    layer turns them under the kernels, or by the pair form — scores x
    64^-1/2."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.float32, sharding=v5e)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.float32, sharding=v5e)

    def loss(q, k, v):
        if form == "lanes":
            cos_sin = rope.tables(8192, 64, 1e6)
            q, k = rope.rope(q, cos_sin), rope.rope(k, cos_sin)
        else:
            q, k = rotary(q, 1e6), rotary(k, 1e6)
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=0.125))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls


@pytest.mark.parametrize("cell,B,T,H,D,Dv,window", [
    # one of `st`'s two windows a step: the second changes no step's
    # geometry (held below) and makes the compile 55 s where this is 2
    ("st", 1, 16384, 28, 128, 128, None),     # its global layer
    ("st_band", 1, 16384, 28, 128, 128, 4096),    # its three window layers
    ("ou", 1, 8192, 16, 128, 128, None),
    ("km", 1, 8192, 16, 192, 128, None),      # the latent's two widths
    # twice `km`'s heads and twice its window: tiles no other cell runs
    ("kl", 1, 16384, 32, 192, 128, None),
    ("sf", 4, 1024, 16, 64, 64, None),        # heads of 64, several a step
])
def test_the_fused_flash_backward_lowers_for_v5e(v5e, cell, B, T, H, D, Dv,
                                                 window):
    """The one backward kernel at the cells' shapes (k and v as the
    kernels see them, repeated): Mosaic takes the whole `[1, T, G·D]`
    float32 dQ column as an output block that stays put along a walk,
    the dynamic row slice its tiles add into, and the scoped VMEM the
    call STATES — its counted bytes, the column twice in the count, and
    the margin — where its default of 16 MiB would refuse the column
    alone; the compiled gradient holds the forward and
    `iotml_flash_bwd_fused` and neither of the two."""
    q, k = (jax.ShapeDtypeStruct((B, T, H, D), jnp.float32, sharding=v5e),) * 2
    v = jax.ShapeDtypeStruct((B, T, H, Dv), jnp.float32, sharding=v5e)
    geom = attention.flash_geometry("bwd_fused", T, D, 4, B, H, True, Dv=Dv,
                                    window=window)
    more = attention.flash_geometry("bwd_fused", T, D, 4, 2 * B, H, True,
                                    Dv=Dv, window=window)
    assert (more.block_q, more.block_k, more.heads, more.tiles) == (
        geom.block_q, geom.block_k, geom.heads, geom.tiles)
    limit = attention._VMEM_MARGIN + attention._vmem_bytes(
        "bwd_fused", geom.block_q, geom.block_k, geom.heads, D, 4, Dv,
        geom.t_q)
    assert 2 * T * geom.heads * D * 4 < limit <= 96 * 2 ** 20

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v)
    said = re.findall(r"scoped_memory_configs[^\]]*?size[^:\]]*: (\d+)",
                      lowered.as_text())
    assert said == [str(limit)]     # the forward states none
    calls = [line for line in lowered.compile().as_text().splitlines()
             if " custom-call(" in line]
    assert [sum(name in line for line in calls) for name in (
        attention.FWD_KERNEL, attention.BWD_FUSED_KERNEL,
        attention.BWD_DKV_KERNEL, attention.BWD_DQ_KERNEL)] == [1, 1, 0, 0]


@pytest.mark.parametrize("shape", [
    (1, 8192, 16, 128),    # `ou-train-backlog`'s q and k, exactly
    (2, 8192, 32, 64),     # `lf-train-backlog`'s q: two heads a chunk
    (2, 8192, 8, 64),      # and its k: 512 lanes, blocks of 1,024 rows
    (1, 8200, 4, 256),     # a head of two tiles' lanes; a ragged block
])
def test_rope_lowers_for_v5e(v5e, shape):
    """`iotml_rope` forward and backward (the same call turned back):
    the lane rotations, the parity select and the loop over a block's
    rows pass Mosaic, and the blocks fit scoped VMEM."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)

    def loss(x):
        cos_sin = rope.tables(shape[1], shape[3], 1e6)
        return jnp.sum(rope.rope(x, cos_sin) ** 2)

    text = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert text.count(rope.ROPE_KERNEL) >= 2


def test_a_turned_layer_keeps_its_stream_feature_minor(v5e):
    """What `iotml_rope` exists to remove, held without a chip: one
    attention layer at `ou-train-backlog`'s shape — the four
    projections, q and k turned, the flash kernels, forward and backward
    — compiled for the described v5e.  Under XLA's pair form the
    program held `f32[1,8192,16,64,2]` arrays, which XLA lays out with T
    on the lanes, and copies of the `[1, 8192, 2048]` stream into and
    out of that layout on both sides of the kernels; now no array ends
    in an axis of 2, and no `copy` makes the stream T-minor."""
    from iotml.models.hybrid import GroupedAttention, HybridConfig

    cfg = HybridConfig(d_model=2048, num_heads=16, num_kv_heads=16,
                       head_dim=128, attn_rope_theta=1e6)
    layer = GroupedAttention(cfg, "flash")
    u = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32, sharding=v5e)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), u))

    def loss(p, u):
        return jnp.sum(layer.apply(p, u) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    assert text.count(rope.ROPE_KERNEL) >= 4    # q, k: forward, backward
    shaped = re.compile(r" = (\w+)\[([\d,]+)\]\{([\d,]+)[:}]")
    pairs, t_minor = [], []
    for line in text.splitlines():
        found = shaped.search(line)
        if not found:
            continue
        dims = [int(d) for d in found.group(2).split(",")]
        order = [int(d) for d in found.group(3).split(",")]
        if len(dims) > 2 and dims[-1] == 2 and 8192 in dims:
            pairs.append(line.strip()[:120])
        if " copy(" in line and dims == [1, 8192, 2048] and order[0] == 1:
            t_minor.append(line.strip()[:120])
    assert not pairs, pairs[:3]
    assert not t_minor, t_minor[:3]


@pytest.mark.parametrize("shape,splits,K,activation,copies", [
    # `gh-train-backlog`, exactly: the convolved stream as the mixer
    # hands it over, out as x, B and C (the B and C runs read from row
    # blocks 32 and 33 of it)
    ((1, 4096, 4352), (4096, 128, 128), 4, "silu", (0, 0)),
    # `lf-train-backlog`, exactly: the gated stream of a short
    # convolution, two windows of 8,192 positions in blocks of 4,096 (a
    # lane tile ahead of each second block), three taps, the sum stored
    # as it stands
    ((2, 8192, 2048), (2048,), 3, "none", (0, 0)),
    # blocks with a lane tile ahead of them: T past the longest block
    ((2, 16384, 512), (512,), 4, "silu", (0, 0)),
    # `kl-train-backlog`, exactly: q, k and v of a delta-rule mixer,
    # three runs of 4,096 channels of one product, read where they lie
    ((1, 16384, 12288), (4096,) * 3, 4, "silu", (0, 0)),
    # sublanes that divide nothing and positions that fill no lane
    # tile: every run sliced out and padded ahead of the kernels
    ((2, 203, 256), (200, 56), 4, "silu", (2, 4)),
    ((2, 203, 256), (200, 56), 3, "none", (2, 4)),
    ((3, 5, 80), (80,), 2, "silu", (1, 2)),
])
def test_conv_kernels_lower_for_v5e(v5e, monkeypatch, shape, splits, K,
                                    activation, copies):
    """`iotml_conv_fwd` and `iotml_conv_bwd` through the entry point and
    `jax.grad`: a lane roll or a dynamic slice Mosaic cannot place, a
    block `conv_geometry` sized past scoped VMEM or a row block it
    refuses fail here and not on the chip.  The backend here is the
    CPU, so the one place that decides is told to compile."""
    from iotml.obs.metrics import default_registry

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    C = sum(splits)
    x, kernel, bias = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=v5e)
                       for s in (shape, (K, C), (C,)))

    def loss(x, kernel, bias):
        return sum(jnp.sum(y * y) for y in causal_conv1d_fused(
            x, kernel, bias, splits=splits, activation=activation))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, kernel, bias).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 2 * len(splits)
    said = default_registry.collect()
    assert (said['iotml_conv_operand_copies{kernel="fwd"}'],
            said['iotml_conv_operand_copies{kernel="bwd"}']) == copies
    assert (said["iotml_conv_taps"], said["iotml_conv_activation_fused"]) \
        == (K, activation == "silu")


def _compiled_fit(v5e, model, T: int, B: int = 1) -> str:
    """The scanned fit of `model` compiled for the described v5e from
    shapes alone: its text."""
    return _lowered_fit(v5e, model, T, B).compile().as_text()


def _lowered_fit(v5e, model, T: int, B: int = 1):
    """The scanned fit of `model` — four steps of B windows of T
    positions, two epochs, Adam and all — lowered for the described
    v5e from shapes alone."""
    import optax

    from iotml.train.loop import TrainState, make_scanned_fit

    tx = optax.adam(1e-5)

    def fresh(rng, x):
        params = model.init(rng, x)["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), apply_fn=model.apply,
                          tx=tx)

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    state = described(jax.eval_shape(fresh, jax.random.PRNGKey(0),
                                     jnp.zeros((B, T, 18))))
    xs, ys, masks = described(tuple(
        jax.ShapeDtypeStruct(s, jnp.float32)
        for s in ((4, B, T, 18), (4, B, 1, 18), (4, B))))
    return make_scanned_fit(model, tx, supervised=True).lower(
        state, xs, ys, masks, epochs=2)


@pytest.mark.parametrize("L,H,chunk", [
    (1024, 32, 64),     # `kl-train-backlog`'s segment, exactly
    (256, 4, 16),       # eight chunks a step, four heads: all of them
    (128, 8, 128),      # a chunk a step: three merges
])
def test_the_delta_rule_kernels_lower_for_v5e(v5e, monkeypatch, L, H, chunk):
    """`iotml_kda_intra_fwd` and `iotml_kda_intra_bwd` through the entry
    point and `jax.grad`, a segment `[1, L, H·128]` as the scan hands it
    over: a lane gather, a turned tile or a lane roll Mosaic cannot
    place, or blocks past the VMEM the calls ask for, fail here and not
    on the chip."""
    from iotml.ops import delta

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    geom = delta.intra_geometry(L, H, 128, 128, chunk, False)
    assert geom == (128, min(H, 8))
    wide, beta = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=v5e)
                  for s in ((1, L, H * 128), (1, L, H)))

    def loss(q, k, G, v, beta):
        return sum(jnp.sum(a * a) for a in delta.kda_intra(
            q, k, G, v, beta, chunk, geom))

    lowered = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        wide, wide, wide, wide, beta)
    bodies = re.findall(r'kernel_name = "(iotml_\w+)"', lowered.as_text())
    assert sorted(bodies) == [delta.KDA_BWD_KERNEL, delta.KDA_FWD_KERNEL]
    assert lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 2


def test_the_delta_rule_fit_lowers_for_v5e_chunked_and_unturned(
        v5e, monkeypatch):
    """`kl-train-backlog`'s whole fit at the published widths — four
    delta-rule layers, one latent layer, a dense MLP and four expert
    layers, Adam and all — lowered for the described v5e from shapes
    alone: the scan says 256 chunks of 64 a window and the sixteen
    states it keeps, its inner part is the two kernels — each says the
    512 head-chunks a segment's call covers, the backward's body is in
    the module once and the forward's once a context (the pass, the
    block's recomputation, the segment's: jitted calls, as the fused
    flash backward's), twelve calls of the forward and four of the
    backward, and compiled the scope's only `[…, 64, 64]` blocks are the
    scores the forward calls return (none is XLA's own) — the latent
    layer turned nothing (no `rope` scope in the module, no call of
    `ops.moe.rotary` while it was traced), the convolutions and the latent layer's flash kernels are Pallas calls,
    and no loop of the module steps the window's 16,384 positions.
    COMPILED, the fit's temporaries are stated against what the byte
    budget counted: it set a delta-rule mixer's backward (3.22 GB)
    aside and bought four shared experts' first products, the compiled
    temporaries beyond every kept name are no less than that backward,
    and with a trainer's state they leave a GiB of the chip free."""
    import json

    from iotml.models import hybrid
    from iotml.models.hybrid import SensorHybrid
    from iotml.obs.metrics import default_registry
    from iotml.ops import moe

    def never(*a, **k):
        raise AssertionError("a rotary turn in a stack without positions")

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    monkeypatch.setattr(moe, "rotary", never)
    chip = 16_909_336_064            # a v5e's `bytes_limit`, as runs read it
    monkeypatch.setattr(hybrid, "device_bytes", lambda: chip)
    stem = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "sensorformer-kimi-linear-48b-a3b")
    with open(stem + ".json") as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location("bench_kl_aot",
                                                  stem + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    job = cfg["job"]
    model = SensorHybrid(mod.hybrid_config(cfg), attn_mode="flash")
    jax.clear_caches()
    lowered = _lowered_fit(v5e, model, job["window"], job["batch_size"])
    text = lowered.as_text()
    said = default_registry.collect()
    assert (said["iotml_kda_chunk_size"], said["iotml_kda_chunks"]) \
        == (64, 256)
    assert said["iotml_kda_state_bytes"] \
        == 16 * job["batch_size"] * 32 * 128 * 128 * 4
    assert said['iotml_model_layers{kind="kda"}'] == 4
    assert said["iotml_model_mla_rope"] == 0 and "rope" not in text
    bodies = re.findall(r'kernel_name = "(iotml_\w+)"', text)
    assert {"iotml_conv_fwd", "iotml_conv_bwd", "iotml_flash_fwd",
            "iotml_flash_bwd_fused"} <= set(bodies)
    # the inner part: both kernels engaged, one body and one function each
    assert said['iotml_kda_intra_kernel{direction="fwd"}'] == 16 * 32
    assert said['iotml_kda_intra_kernel{direction="bwd"}'] == 16 * 32
    # (jitted, so traced ONCE; jax stages a jitted call's jaxpr anew
    # where a recomputation re-derives it, so the module holds the
    # forward's function once a context — the pass itself, the block's
    # recomputation, the segment's — each called by the four layers)
    for kernel, function, copies, calls in (
            ("iotml_kda_intra_fwd", "_intra_forward", 3, 12),
            ("iotml_kda_intra_bwd", "_intra_backward", 1, 4)):
        assert bodies.count(kernel) == copies
        assert len(re.findall(rf"func\.func private @{function}(_\d+)?\(",
                              text)) == copies
        assert len(re.findall(rf"call @{function}(_\d+)?\(", text)) == calls
    # the loops' trip counts are constants of the module (the experts'
    # walks alone stop on data): the fit's epochs and batches, a window's
    # sixteen segments and a segment's sixteen chunks — never its positions
    trips = set(re.findall(r"cond \{\s+%\w+ = stablehlo\.constant "
                           r"dense<(\d+)> : tensor<i32>", text))
    assert {"16"} <= trips <= {"2", "4", "16"}
    # the budget: what it set aside, what it bought, what was compiled
    tokens = job["batch_size"] * job["window"]
    backward = hybrid.backward_bytes(model.cfg, tokens, 4)
    assert backward == 12 * tokens * 4096 * 4
    assert said['iotml_remat_kept_layers{kind="ffn"}'] == 4
    assert said['iotml_remat_kept_bytes{kind="ffn"}'] \
        == 4 * tokens * 2 * 1024 * 4
    kept = sum(v for k, v in said.items()
               if k.startswith("iotml_remat_kept_bytes"))
    compiled = lowered.compile()
    # … but the queries' scores a forward call hands to `kda_out`: no
    # fusion, copy or product of XLA's makes a block of scores there
    blocks = re.findall(
        r"^.* = f32\[[\d,]*64,64\]\S* ([\w\-]+)\(([^\n]*kda_intra[^\n]*)$",
        compiled.as_text(), re.M)
    assert len(blocks) == 12 and all(
        op == "get-tuple-element" and "iotml_kda_intra_fwd" in rest
        for op, rest in blocks)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes - kept >= backward
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        <= chip - 2 ** 30


def test_the_fit_keeps_the_mixers_stream_time_minor(v5e, monkeypatch):
    """What `iotml_conv_operand_copies` cannot see: the kernels work on
    [B, channels, T] because that is how XLA lays the mixer's tensors
    out in the compiled fit, so the `swapaxes` around the calls are
    bitcasts.  A transposing copy at an edge has a channel-minor array
    on one side, and on [B, T, channels] rows the cell's fit ran 5%
    slower than with no kernel at all (PERF.md §6, PR 29): so the
    scanned fit of one Mamba block at `gh-train-backlog`'s widths, Adam
    and all, compiled for the described v5e, holds no array of the
    stream's size — `in_proj`'s [1, 4096, 8512] product, the
    [1, 4096, 4352] the convolution reads and writes, their cotangents
    — with the channels fastest."""
    from iotml.models.hybrid import HybridConfig, SensorHybrid

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    T = 4096
    model = SensorHybrid(HybridConfig(
        d_model=2048, layer_types=("mamba",), num_heads=32, num_kv_heads=8,
        mlp_dim=8192, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
        conv_width=4, chunk=256))
    text = _compiled_fit(v5e, model, T)
    assert "iotml_conv_fwd" in text and "iotml_conv_bwd" in text
    # minor-to-major {1,2,0} of [1, T, C] and {2,1,0} of [1, C, T] are
    # the same bytes: time fastest
    laid = set(re.findall(r"f32\[1,(?:4096,(?:4352|8512)|(?:4352|8512),4096)"
                          r"\]\{[\d,]+", text))
    assert laid and laid <= {"f32[1,4096,4352]{1,2,0", "f32[1,4352,4096]{2,1,0",
                             "f32[1,4096,8512]{1,2,0", "f32[1,8512,4096]{2,1,0"}


def test_the_fit_keeps_the_short_convolutions_stream_time_minor(
        v5e, monkeypatch):
    """The gated short convolution hands the kernels `b ⊙ x` and takes
    y back through the same `swapaxes` as the Mamba mixer: in the
    scanned fit of `lf-train-backlog`'s first layer (the mixer and the
    dense MLP at the published widths, two windows of 8,192, Adam and
    all) compiled for the described v5e, `in_proj`'s [2, 8192, 6144]
    product and its cotangent exist time-fastest only, and every
    convolution call reads and writes [2, 2048, 8192] row-major — so no
    transposing copy stands at a call's edge."""
    from iotml.models.hybrid import HybridConfig, SensorHybrid

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    model = SensorHybrid(HybridConfig(
        d_model=2048, layer_types=("short_conv",), mlp_dim=11776,
        short_conv_width=3, embedding_multiplier=1.0,
        residual_multiplier=1.0, logits_scaling=1.0))
    text = _compiled_fit(v5e, model, 8192, B=2)
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "iotml_conv_" in line]
    assert sum("iotml_conv_fwd" in c for c in calls) == 2   # and recomputed
    assert sum("iotml_conv_bwd" in c for c in calls) == 1
    for call in calls:
        assert "f32[2,2048,8192]{2,1,0" in call.split(" custom-call(")[0]
    laid = set(re.findall(r"f32\[2,(?:8192,6144|6144,8192)\]\{[\d,]+", text))
    assert laid and laid <= {"f32[2,8192,6144]{1,2,0",
                             "f32[2,6144,8192]{2,1,0"}


def test_the_fit_runs_the_flash_forward_once_a_layer(v5e, monkeypatch):
    """What `tests/test_remat_policy.py` counts in the gradient's jaxpr,
    held on the compiled program: the scanned fit of one block of latent
    attention and experts at `km-train-backlog`'s widths, Adam and all,
    compiled for the described v5e, calls `iotml_flash_fwd` once — the
    block's recomputation keeps the kernel's `out` and log-sum-exp
    (`models.hybrid.KEPT`) and reads them where it ran the kernel a
    second time — and sorts the layer's assignments three times, top-k's
    sort, the plan's (the routing weights its third operand) and the one
    that brings the weights' cotangents back, where it sorted them four
    times and gathered and scatter-added the 49,152 weights a scalar at
    a time."""
    from iotml.models.hybrid import HybridConfig, SensorHybrid

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    T = 8192
    model = SensorHybrid(HybridConfig(
        d_model=2048, layer_types=("mla",), ffn_types=("moe_ffn",),
        num_heads=16, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        rope_theta=800000.0, experts=64, experts_held=(0, 8), top_k=6,
        expert_dim=1408, shared_dim=2816, routed_scale=2.446,
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0), attn_mode="flash")
    lines = _compiled_fit(v5e, model, T).splitlines()
    calls = [line for line in lines if " custom-call(" in line]
    for kernel, count in (("iotml_flash_fwd", 1), ("iotml_flash_bwd_fused", 1),
                          ("iotml_flash_bwd_dkv", 0), ("iotml_flash_bwd_dq", 0)):
        assert sum(kernel in line for line in calls) == count, kernel
    sorts = [line for line in lines if re.search(r" = .* sort\(", line)]
    assert len(sorts) == 3
    assert sum("f32[49152]" in line.split(" sort(")[0] for line in sorts) == 2
    assert not [line for line in lines if re.search(
        r" = f32\[49152\]\S* (gather|scatter)\(", line)]


def test_the_looped_fit_runs_the_flash_forward_once_an_application(
        v5e, monkeypatch):
    """A stack whose layers are run several times a step is ONE pass's
    program under a scan: the scanned fit of two sandwich-normed layers
    at `ou-train-backlog`'s widths (sixteen heads of 128 on sixteen
    key/value heads, rotary over the whole head, an MLP of 5,632), two
    passes, the expected loss and Adam and all, compiled for the
    described v5e, holds each flash kernel once a LAYER — inside the
    passes' loop, so once an application, the forward not again in the
    backward's recomputation (its `out` and log-sum-exp come back
    stacked a pass) — and hands the kernels q, k and v as the
    projections made them: no operand copied."""
    from iotml.models.hybrid import HybridConfig, SensorHybrid
    from iotml.obs.metrics import default_registry

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    jax.clear_caches()   # the geometry is said where a shape is traced
    model = SensorHybrid(HybridConfig(
        d_model=2048, layer_types=("attention",) * 2, num_heads=16,
        num_kv_heads=16, head_dim=128, attention_multiplier=128 ** -0.5,
        attn_rope_theta=1000000.0, mlp_dim=5632, eps=1e-6, loop_steps=2,
        post_norms=True, embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0), attn_mode="flash")
    lines = _compiled_fit(v5e, model, 8192).splitlines()
    # by the instruction's own name: a call's operands are named too
    # (`iotml_rope` reads `%iotml_flash_bwd_fused.n`)
    calls = [line.split(" = ")[0] for line in lines
             if " custom-call(" in line]
    for kernel, count in (("iotml_flash_fwd", 2), ("iotml_flash_bwd_fused", 2),
                          ("iotml_flash_bwd_dkv", 0), ("iotml_flash_bwd_dq", 0)):
        assert sum(kernel in name for name in calls) == count, kernel
    said = default_registry.collect()
    assert said["iotml_flash_backward_fused"] == 1
    assert [said[f'iotml_flash_operand_copies{{kernel="{k}"}}']
            for k in ("fwd", "bwd_fused")] == [0, 0]
    assert said["iotml_model_loop_steps"] == 2
    # q and k turned in the kernels' layout: a layer's forward, its
    # recomputation and its backward hold the call twice each — by
    # tables made outside the passes' loop, not once an application
    assert said["iotml_attn_rotary_kernel"] == 2
    assert sum(rope.ROPE_KERNEL in name for name in calls) == 2 * 6
    assert sum(" cosine(" in line for line in lines) <= 2
    assert not [line for line in lines
                if re.search(r"f32\[1,8192,16,64,2\]", line)]
    # the passes' loop is in the program: the stacked kernel outputs
    assert any(re.search(r"f32\[2,1,8192,16,128\]", line) for line in lines)


def test_the_fit_holds_one_body_of_the_fused_backward_a_shape(
        v5e, monkeypatch):
    """`_flash_backward` is jitted, so a stack of many layers traces
    and lowers the one backward kernel once a SHAPE and not once a
    layer: the lowered fit of a global layer and three window layers —
    `st-train-backlog`'s `G W W W` at small widths — holds two bodies of
    `iotml_flash_bwd_fused` (the triangle's grid, the band's), four
    calls of them, and no body of the two-kernel form."""
    from iotml.models.hybrid import HybridConfig, SensorHybrid

    monkeypatch.setattr(fused_train, "interpret_mode", lambda: False)
    jax.clear_caches()
    model = SensorHybrid(HybridConfig(
        d_model=256, layer_types=("attention",) + ("window_attention",) * 3,
        num_heads=2, num_kv_heads=1, head_dim=128, attn_window=256,
        mlp_dim=512, embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0), attn_mode="flash")
    text = _lowered_fit(v5e, model, 1024).as_text()
    bodies = re.findall(r'kernel_name = "(iotml_flash_\w+)"', text)
    assert sorted(bodies) == ["iotml_flash_bwd_fused"] * 2 \
        + ["iotml_flash_fwd"] * 2
    assert len(re.findall(r"func\.func private @_flash_backward", text)) == 2
    assert len(re.findall(r"call @_flash_backward", text)) == 4


def _called(text: str, name: str) -> str:
    """The computation `name` of a compiled module's text, and every
    computation it calls, fusions' bodies among them."""
    bodies = dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
                             re.M | re.S))
    seen, todo = [], [name]
    while todo:
        at = todo.pop()
        if at in seen or at not in bodies:
            continue
        seen.append(at)
        todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                           bodies[at])
    return "\n".join(bodies[at] for at in seen)


@pytest.mark.parametrize("stem,tokens,d,form", [
    ("sensorformer-smallthinker-21b-a3b", 32768, 2560, "kernel"),
    ("sensorformer-lfm2-24b-a2b", 16384, 2048, "kernel"),
    # 64 and 32 MiB of accumulator: the rule leaves these two to XLA
    # (it keeps them in VMEM); the kernel lowers at their shapes too
    ("sensorformer-kimi-vl-a3b-instruct", 8192, 2048, "scatter"),
    ("sensorformer-nemotron-3-super-120b-a12b", 8192, 1024, "scatter"),
])
def test_add_rows_in_a_tile_loop_lowers_for_v5e_in_place(v5e, stem, tokens,
                                                         d, form):
    """`iotml_add_rows` the way the experts' walk calls it — inside a
    `fori_loop` whose trip count is data, the accumulator the loop's
    carry, two such loops as a layer's forward and backward are — at the
    four sparse-expert cells' shapes, read off their configuration
    files: Mosaic takes the row copies (a row of the carried `[N, 1, d]`
    is one piece: a row of `[N, d]`, a sublane of an (8, 128) tile, it
    refuses), the lowered module holds ONE function `add_rows`, with the
    one kernel in it, that both loops' bodies call (`add_rows.add_rows`
    is jitted: a call site more is a `func.call` more, not the kernel
    traced and lowered again), and the alias holds through that call and
    the carry — each compiled loop's body holds the call and NO copy of
    the accumulator (335 MB a tile at `st`'s shape, where a tile's add
    moves 16)."""
    import json

    from iotml.ops import moe

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", stem + ".json")) as f:
        cfg = json.load(f)
    job = cfg["job"]
    assert job["batch_size"] * job["window"] == tokens
    assert (cfg.get("moe_latent_size") or cfg["hidden_size"]) == d
    tile, tiles = moe._tile(tokens), 24
    assert moe.add_rows_form(tokens, d, jnp.float32, "flash") == form

    def walk(x, d_out, token, tile_rows, live_tiles):
        def loop(of):
            def tile_step(c, acc):
                at = jax.lax.dynamic_slice_in_dim(token, c * tile, tile)
                return moe._add_rows(acc, at, tile_rows[c],
                                     of[:tile] * tile_rows[c], "kernel")
            return jax.lax.fori_loop(0, live_tiles, tile_step,
                                     moe._accumulator(of, "kernel")
                                     ).reshape(of.shape)
        return loop(x), loop(d_out)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    lowered = jax.jit(walk).lower(
        sds((tokens, d)), sds((tokens, d)), sds((tiles * tile,), jnp.int32),
        sds((tiles,), jnp.int32), sds((), jnp.int32))
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @add_rows\(", text)) == 1
    assert len(re.findall(r"call @add_rows\(", text)) == 2
    assert len(re.findall(r"kernel_name = \"iotml_add_rows\"", text)) == 1
    text = lowered.compile().as_text()
    loops = re.findall(r" while\([^\n]*body=%?([\w.\-]+)", text)
    assert len(loops) == 2
    carried = rf"f32\[{tokens},(?:1,)?{d}\]"
    for loop in loops:
        body = _called(text, loop)
        assert len(re.findall(r"custom-call\([^\n]*iotml_add_rows",
                              body)) == 1
        assert re.search(carried + r"[^\n]* custom-call\(", body)
        assert not re.search(carried + r"\S* copy\(", body)
