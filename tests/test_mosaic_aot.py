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

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from iotml.ops import fused_train
from iotml.ops.attention import flash_attention


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


def test_flash_attention_grouped_heads_lower_for_v5e(v5e):
    """`gh-train-backlog`'s one attention layer, exactly, forward and
    backward: 32 query heads over 8 key/value heads of 64 at T = 4,096,
    the scale the source names."""
    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.float32, sharding=v5e)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.float32, sharding=v5e)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=0.015625))

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
