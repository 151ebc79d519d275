"""A tile's rows added back onto their tokens: the Pallas call
`iotml_add_rows`.

The experts' tile loops (`ops/moe.py` `_experts`, `_experts_bwd`) end a
tile with `acc.at[tokens].add(rows)`: up to 512 distinct rows of an
`[N, d]` float32 accumulator, each `rows`' row added onto it.  XLA's
scatter emitter on a TPU takes them a row at a time, 0.29 µs a 10 KB
row into an accumulator in HBM where its gather of the same rows takes
0.027 (PERF.md §6, PR 47).

Here the same add moves rows the way the gather does, by DMA.  The
accumulator is `[N, 1, d]`: Mosaic refuses a one-row slice of an
(8, 128)-tiled `[N, d]` (a row there is a sublane of `d / 128` tiles),
and lays `[N, 1, d]` out a row a piece (`T(1,128)`), which a copy can
name.  It stays in HBM and IS the output (`input_output_aliases`: the
loop's carry is updated in place); the tile's tokens and its count of
live rows arrive by scalar prefetch; `rows` comes into VMEM a chunk of
rows a grid step, by the pipeline.  A step starts a copy HBM → VMEM a
live row of the NEXT chunk, waits for this chunk's, adds `rows`' rows
onto them, and starts the copies back — in a ring of `_SLOTS` buffers,
so the reads of the next chunk and the writes of the last overlap the
add of this one.  What a tile costs is the 1,024 copies the scalar core
starts, not their bytes: 33 µs at 10 KB a row, 21 at 4 KB, whatever the
ring's and the chunk's size.  Rows are distinct inside a tile (a token
meets an expert once) and tiles run one after another, so nothing has
to be atomic.  A tile's padding (`tokens` past the live rows) is never
touched: `mode="drop"`.  One float32 add an element, in the tile's
order: XLA's result bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the kernel's name in a device trace and in the HLO
ADD_ROWS_KERNEL = "iotml_add_rows"

#: bytes of a chunk of rows: a ring slot of the accumulator's rows, and
#: a block of `rows` (two of those, the pipeline's).  1 MiB: 64 rows of
#: 2,560 float32 lanes, 128 of 2,048, 256 of 1,024 — five such buffers
#: are a third of the 16 MiB of scoped VMEM
_CHUNK_BYTES = 2 ** 20
#: buffers in the ring: the chunk being read, the one added, the one
#: written back
_SLOTS = 3
#: an accumulator up to this size is better left to XLA: it keeps the
#: loop's carry in VMEM (a v5e has 128 MiB: `km-train-backlog`'s 64 MiB
#: carry reads `S(1)` in the compiled fit), where its scatter-add takes
#: 40-49 µs a tile against the kernel's 21-25, and that gain is less
#: than what the kernel's `[N, 1, d]` layout costs a loop (zeros written
#: and the result relaid at an eighth of a vector register) — `km` ran
#: 0.8% slower under the kernel, `ns` 0.6% faster (PERF.md §6, PR 47)
RESIDENT_BYTES = 64 * 2 ** 20


def chunk_rows(tile: int, d: int, itemsize: int) -> int:
    """Rows of a chunk for a tile of `tile` rows `d` wide — or 0 where
    the kernel cannot take the tile and the caller keeps XLA's
    scatter-add: four-byte elements (two-byte rows pack two to a
    sublane), rows of whole 128-lane tiles, and a tile of whole 8-row
    sublane tiles, as `rows`' blocks are.  The largest such divisor of
    the tile inside `_CHUNK_BYTES`."""
    if itemsize != 4 or d % 128 or tile % 8:
        return 0
    fits = [c for c in range(8, tile + 1, 8)
            if tile % c == 0 and c * d * itemsize <= _CHUNK_BYTES]
    return max(fits, default=0)


def _step(tokens_ref, live_ref, rows_ref, acc_ref, out_ref, ring, gathered,
          scattered, *, chunk: int, chunks: int):
    """Grid step i adds chunk i of the tile.  `out_ref` is the
    accumulator (aliased to `acc_ref`, which is not read), in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del acc_ref
    i = pl.program_id(0)

    def live_rows(c):
        return jnp.clip(live_ref[0] - c * chunk, 0, chunk)

    def start(c, back: bool):
        """Start the copies of chunk c's live rows, a row a copy: the
        accumulator's row → the row of the chunk's ring slot, or `back`."""
        slot = c % _SLOTS

        def one(r, _):
            hbm = out_ref.at[pl.ds(tokens_ref[c * chunk + r], 1)]
            vmem = ring.at[slot, pl.ds(r, 1)]
            (pltpu.make_async_copy(vmem, hbm, scattered.at[slot]) if back
             else pltpu.make_async_copy(hbm, vmem, gathered.at[slot])).start()

        jax.lax.fori_loop(0, live_rows(c), one, None)

    def wait(c, back: bool):
        """Wait for chunk c's copies.  A slot's copies signal one
        semaphore by their bytes and a wait takes its copy's bytes off
        it, whichever rows that copy names: so one wait a set bit of
        the live rows' count, for that many rows' bytes, and not one a
        row (7 µs of a 40 µs tile at `st-train-backlog`'s shape)."""
        slot, live = c % _SLOTS, live_rows(c)
        for bit in range(chunk.bit_length()):
            @pl.when(live & (1 << bit) != 0)
            def _():
                rows = ring.at[slot, pl.ds(0, 1 << bit)]
                pltpu.make_async_copy(
                    rows, rows, (scattered if back else gathered).at[slot]
                ).wait()

    @pl.when(i == 0)
    def _():
        start(i, back=False)

    # chunk i + 1 reads into the slot chunk i + 1 − _SLOTS wrote from
    @pl.when(i + 1 >= _SLOTS)
    def _():
        wait(i + 1 - _SLOTS, back=True)

    @pl.when(i + 1 < chunks)
    def _():
        start(i + 1, back=False)

    wait(i, back=False)

    def add_one(r, _):
        ring[i % _SLOTS, r] = ring[i % _SLOTS, r] + rows_ref[pl.ds(r, 1), :]

    jax.lax.fori_loop(0, live_rows(i), add_one, None)

    start(i, back=True)

    @pl.when(i == chunks - 1)
    def _():
        for c in range(max(0, chunks - _SLOTS + 1), chunks):
            wait(c, back=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_rows(acc, tokens, live, rows, interpret: bool = False):
    """`acc[tokens[:live], 0] += rows[:live]` for acc [N, 1, d], tokens
    [tile] int32 (distinct below `live`; whatever lies past it is
    padding and is not read), rows [tile, d]: `iotml_add_rows`, in place
    on `acc` where the caller's buffer can be reused.  For tiles
    `chunk_rows` accepts.  `interpret=True` runs the same kernel on a
    CPU.

    Jitted, as the flash kernels' calls and `iotml_rope`'s are, and for
    set-up's sake: a fit calls this from every expert layer's forward,
    its recomputed forward and its backward (twelve sites in
    `st-train-backlog`), and a bare `pl.pallas_call` is traced at every
    one of them and lowered at most — 3.65 s of `setup_s` on the chip's
    host, which refused PR 47.  Under `jax.jit` the body (`_step`) is
    traced once a shape and the module holds one function the loops
    call.  The cache's key is the operands' shapes and `interpret`
    alone: the kernel's geometry is derived here, from the shapes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, d = rows.shape
    chunk = chunk_rows(tile, d, acc.dtype.itemsize)
    if not chunk or acc.dtype != rows.dtype or acc.shape[1:] != (1, d):
        raise ValueError(f"iotml_add_rows cannot add rows {rows.shape} "
                         f"{rows.dtype} onto {acc.shape} {acc.dtype}")
    chunks = tile // chunk
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_step, chunk=chunk, chunks=chunks),
        name=ADD_ROWS_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(chunks,),
            # a chunk past the live rows asks for the last live chunk's
            # block again: the pipeline fetches nothing for it
            in_specs=[pl.BlockSpec(
                (chunk, d), lambda i, tokens, live: (jnp.minimum(
                    i, jnp.maximum(live[0] - 1, 0) // chunk), 0)), hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((_SLOTS, chunk, 1, d), acc.dtype),
                            pltpu.SemaphoreType.DMA((_SLOTS,)),
                            pltpu.SemaphoreType.DMA((_SLOTS,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        # operands count the two prefetched scalars first
        input_output_aliases={3: 0},
        # for XLA's scheduler (`ops/rope.py`): the tile's rows in, the
        # accumulator's rows in and out
        cost_estimate=pl.CostEstimate(
            flops=rows.size, transcendentals=0,
            bytes_accessed=3 * rows.size * rows.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tokens.astype(jnp.int32), jnp.reshape(live, (1,)).astype(jnp.int32),
      rows, acc)
