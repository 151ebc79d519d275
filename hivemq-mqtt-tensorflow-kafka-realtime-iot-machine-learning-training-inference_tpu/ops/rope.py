"""Rotary positions in the flash kernels' own layout: the Pallas call
`iotml_rope`.

`ops/moe.py` `rotary` takes a head's pairs `(x[2i], x[2i+1])` by a
reshape to `[…, R/2, 2]`.  On a TPU a minor axis of 2 has no tile: XLA
lays that array out with T on the lanes and pays padded, transposing
copies of the stream on both sides of it — between the projections and
the flash kernels, which index the projections' `[B, T, H·D]` in place
(`ops/attention.py`), forward and backward.

Here the same turn runs on `[B, T, H·D]` as it stands, feature-minor,
whole 128-lane tiles in and out.  A lane's partner in its pair is its
neighbour — lane j + 1 where j is even, j − 1 where it is odd — and a
pair never straddles a tile, so two lane rotations of a tile (by one,
either way) and a select on the lane's parity give the partner, and

    out = x · cos + partner · sin±

with the sign folded into the sine's table (−sin on the even lanes,
+sin on the odd).  Same pairing, float32 angles, tables and arithmetic:
`rotary`'s mathematics.  The tables are `[T, max(R, 128)]` — a head of
128 one period, a head of 64 two — made once by the caller (`tables`)
and read by every head's column block.

Differentiable by a custom VJP: a rotation's transpose is the rotation
back, the same call with the sine's sign turned, on the cotangent.
Nothing of the forward is kept beyond the tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the kernel's name in a device trace and in the HLO
ROPE_KERNEL = "iotml_rope"

#: bytes of a grid step's four x blocks (in and out, each
#: double-buffered by the pipeline): half of the 16 MiB of scoped VMEM,
#: the tables' blocks take 0.5 MiB more — 256 rows of 2,048 float32
#: lanes, 1,024 of 512
_BLOCK_BYTES = 8 * 2 ** 20
_MAX_ROWS = 1024
#: elements of x one pass of the kernel's loop over a block's rows
#: handles (64 float32 vregs: 32 rows of 2,048 lanes, 128 of 512).  A
#: pass is one chain of load, lane rotation, select, products and store,
#: ~100 cycles deep, and passes do not overlap: at a sublane tile of 8
#: rows a pass the call read 572 GB/s at `ou-train-backlog`'s shape, at
#: 32 rows 718, as the whole block unrolled does with sixteen times the
#: code (PERF.md §6, PR 41)
_PASS_ELEMS = 64 * 1024


def lanes(width: int, R: int) -> int:
    """The lanes a row of the tables spans for a `[B, T, width]` array
    of R-wide heads — or 0 where the call cannot turn it in place and
    the caller keeps the pair form: R even and a divisor or a multiple
    of 128, so that a pair's partner is one lane away inside one chunk
    of `max(R, 128)` lanes, and `width` whole such chunks."""
    chunk = max(R, 128)
    if R % 2 or (128 % R and R % 128) or width % chunk:
        return 0
    return chunk


def tables(T: int, R: int, theta: float):
    """float32 `(cos, sin±)`, `[T, max(R, 128)]` each, a head's R lanes
    after another's: lanes 2i and 2i+1 of a head hold the cosine of
    `rotary`'s angle `t · theta^(−2i/R)` and its sine, negated on the
    even lane (out[2i] = a cos − b sin, out[2i+1] = b cos + a sin).
    Made at the lanes' width from the start: no `[…, R/2, 2]` array
    here either."""
    if not lanes(max(R, 128), R):
        raise ValueError(f"iotml_rope cannot turn heads of {R} in place")
    lane = np.arange(max(R, 128))
    evens = jnp.asarray(lane % R // 2 * 2, jnp.float32)   # 0, 0, 2, 2, …
    signs = jnp.asarray(np.where(lane % 2, 1.0, -1.0), jnp.float32)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-evens / R)
    return jnp.cos(angle), signs * jnp.sin(angle)


def _rows(T: int, W: int, itemsize: int) -> tuple:
    """(rows of a block, rows of a pass over it) for x [B, T, W]: whole
    sublane tiles, a block whole passes — or, where T is shorter than a
    block, all of T in one block, in passes only if they divide it."""
    per_pass = max(8, _PASS_ELEMS // W // 8 * 8)
    rows = min(_MAX_ROWS, _BLOCK_BYTES // (4 * W * itemsize))
    block_t = max(per_pass, rows // per_pass * per_pass)
    if T <= block_t:
        return T, per_pass if T % per_pass == 0 else T
    return block_t, per_pass


def _step(x_ref, cos_ref, sin_ref, o_ref, *, rows: int, back: bool):
    """One [block_t, H·D] block, `rows` rows a pass: the tables' rows
    are loaded once a pass and serve every chunk of their lanes across
    the heads; in a chunk a lane's partner is taken by two lane
    rotations and a parity select (a pair never crosses a chunk's edge,
    so the wrapped lanes are never selected), then `x cos ± partner sin`
    in float32 — minus to turn `back`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_t, chunk = cos_ref.shape
    even = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1) % 2 == 0

    def one_pass(r, _):
        at = pl.ds(pl.multiple_of(r * rows, rows), rows)
        cos, sin = cos_ref[at, :], sin_ref[at, :]
        if back:
            sin = -sin
        for c in range(x_ref.shape[2] // chunk):
            cols = slice(c * chunk, (c + 1) * chunk)
            x = x_ref[0, at, cols].astype(jnp.float32)
            partner = jnp.where(even, pltpu.roll(x, chunk - 1, 1),
                                pltpu.roll(x, 1, 1))
            o_ref[0, at, cols] = (x * cos + partner * sin).astype(o_ref.dtype)

    jax.lax.fori_loop(0, block_t // rows, one_pass, None)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _call(x, cos, sin, interpret: bool, back: bool):
    """`iotml_rope` on x [B, T, H·D] with tables [T, chunk]: a grid over
    (T / block, B) — the batch innermost, so a block of the tables is
    fetched once for all rows of the batch.  Jitted, as the flash
    kernels' calls are: equal layers trace and lower it once a shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, W = x.shape
    block_t, rows = _rows(T, W, x.dtype.itemsize)
    x_block = pl.BlockSpec((1, block_t, W), lambda t, b: (b, t, 0))
    table = pl.BlockSpec((block_t, cos.shape[1]), lambda t, b: (t, 0))
    return pl.pallas_call(
        functools.partial(_step, rows=rows, back=back), name=ROPE_KERNEL,
        grid=(pl.cdiv(T, block_t), B),
        in_specs=[x_block, table, table], out_specs=x_block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        # what the call costs, for XLA's scheduler: a custom call without
        # an estimate counts as no time at all, and a prefetch into VMEM
        # that hid behind the pair form's fusions finds nothing to hide
        # behind (`ou-train-backlog`: the recomputed `mlp_in` product read
        # its input from HBM, 54.8 ms a step for 47.1: PERF.md §6, PR 41)
        cost_estimate=pl.CostEstimate(
            flops=6 * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * x.dtype.itemsize + 2 * cos.size * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret)(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _turn(x, cos, sin, interpret: bool, back: bool):
    return _call(x, cos, sin, interpret, back)


def _turn_fwd(x, cos, sin, interpret, back):
    return _call(x, cos, sin, interpret, back), (cos, sin)


def _turn_bwd(interpret, back, res, g):
    # the tables are positions, not parameters: no cotangent
    return _turn(g, *res, interpret, not back), None, None


_turn.defvjp(_turn_fwd, _turn_bwd)


def rope(x, cos_sin, interpret: bool = False):
    """Rotary positions over the heads of x [B, T, H, D] (or the same
    array as `[B, T, H·D]`), positions 0 … T−1 along axis 1, by the
    `tables(T, D, theta)` handed in: one lane-dense Pallas call on the
    projections' own `[B, T, H·D]` (a free reshape), for widths
    `lanes(H·D, D)` accepts.  `interpret=True` runs the same kernel on
    a CPU."""
    cos, sin = cos_sin
    B, T = x.shape[:2]
    flat = x.reshape(B, T, -1)
    if cos.shape[0] != T or flat.shape[2] % cos.shape[1]:
        raise ValueError(
            f"iotml_rope cannot turn {x.shape} by tables {cos.shape}")
    return _turn(flat, cos, sin, interpret, False).reshape(x.shape)
