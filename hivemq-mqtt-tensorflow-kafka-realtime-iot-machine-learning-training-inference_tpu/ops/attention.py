"""Attention ops: reference implementation + Pallas flash-attention kernel.

The reference system's only sequence model is an LSTM at look_back=1
(SURVEY §5 'long-context: nothing') — but this framework treats long per-car
sensor histories as first-class: fleets emit unbounded streams, and anomaly
models that look at hours of context need sequence lengths the LSTM path
never contemplated.  The attention stack here:

- `attention_reference`: straight jnp softmax attention — the oracle for
  every other path, and the XLA-fused fallback on CPU.
- `flash_attention`: blocked online-softmax attention as a Pallas TPU
  kernel — O(T) memory instead of O(T²), the single-chip hot op of the
  transformer model family.  The kernels read q, k, v, dO and write out,
  dq, dk, dv where the projections leave and take them: `[B, T, H, D]`
  seen as `[B, T, H·D]` (a free reshape), a grid step's heads one
  `[1, block, G·D]` column block reached through the index map — no
  transposed copy on either side.  Tiles and heads a step are derived
  from the shape (`flash_geometry`): the heads are what the lanes allow,
  `G·D` a multiple of 128 or the whole width.  The mask is causal over
  the whole past or, with `window`, over the last `window` keys (the
  position itself among them): the grid then walks the live tiles of a
  BAND beside the diagonal where it walked a triangle's, and the edge
  tiles on both sides are masked elementwise.
- `blockwise_update`: one online-softmax accumulation step, shared between
  the flash kernel's inner loop (conceptually) and the ring-attention
  cross-chip loop (`parallel.ring_attention`), which is the same math with
  the KV blocks arriving over ICI instead of from VMEM.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30

#: the kernels' names in a device trace and in the HLO: one per kernel,
#: whichever grid (dense or triangular) runs it; a backward is the fused
#: kernel alone, or the dK/dV and dQ pair
FWD_KERNEL = "iotml_flash_fwd"
BWD_DKV_KERNEL = "iotml_flash_bwd_dkv"
BWD_DQ_KERNEL = "iotml_flash_bwd_dq"
BWD_FUSED_KERNEL = "iotml_flash_bwd_fused"


def _band_edges(nq: int, nk: int, block_q: int, block_k: int, order: str,
                window: Optional[int]) -> tuple:
    """(first, last) live tile of every q row (`row`: kv tiles) or of
    every kv column (`col`: q tiles) under the causal mask, of the last
    `window` keys where one is given: query t meets keys
    `t − window < j ≤ t`.  A column in the future of every query keeps
    its one dead diagonal tile."""
    if order == "row":
        rows = np.arange(nq, dtype=np.int64) * block_q
        last = np.minimum(nk - 1, (rows + block_q - 1) // block_k)
        first = np.zeros_like(last) if window is None else np.minimum(
            last, np.maximum(rows - window + 1, 0) // block_k)
    else:
        cols = np.arange(nk, dtype=np.int64) * block_k
        first = np.minimum(nq - 1, cols // block_q)
        last = np.full_like(first, nq - 1) if window is None else np.minimum(
            nq - 1, (cols + block_k + window - 2) // block_q)
    return first, last


def _causal_tiles(nq: int, nk: int, block_q: int, block_k: int,
                  order: str, window: Optional[int] = None) -> tuple:
    """Enumerate the LIVE causal tiles as (i_map, j_map) int32 arrays:
    the lower triangle's or, under `window`, the band's beside the
    diagonal (`_band_edges`).

    The dense grid pays DMA + a grid step for every (i, j) tile and
    `pl.when`s away the strictly-future half — measured at ≈½ a computed
    tile each (ARCHITECTURE.md roofline lever 2).  Feeding these maps
    through scalar prefetch makes the grid exactly the lower triangle:
    skipped tiles stop existing instead of being masked.

    order="row": row-major (i outer) — forward and dQ, whose scratch
    accumulates along j within one q row.  order="col": column-major
    (j outer) — dK/dV, whose scratch accumulates along i within one kv
    column.  Columns entirely in the future of every query keep one dead
    diagonal tile so their dk/dv output block is still zero-written.

    Cost bound: the maps hold ~nq·nk/2 int32 pairs (vectorized numpy —
    no Python loop), shipped through scalar prefetch.  At the
    long-context shape (T=65,536, 1024² tiles) that is 2,080 tiles =
    16 KB; callers picking tiny blocks at huge T pay O((T/block)²)
    map memory, which `_flash_forward` caps (falls back to the dense
    grid past _TRI_TILE_CAP) so the prefetch stream can never outgrow
    SMEM-class storage."""
    first, last = _band_edges(nq, nk, block_q, block_k, order, window)
    counts = last - first + 1
    outer = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    # the inner index runs first..last within each row or column: a
    # global arange minus the group's start
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    inner = np.arange(counts.sum(), dtype=np.int64) - starts + \
        np.repeat(first, counts)
    im, jm = (outer, inner) if order == "row" else (inner, outer)
    return (im.astype(np.int32), jm.astype(np.int32))


#: triangular-grid cap: above this many live tiles the scalar-prefetch
#: maps (2 × 4 B × tiles, × 3 kernels) would outgrow SMEM-class storage —
#: fall back to the dense grid, which has O(1) grid metadata.  65,536
#: tiles = 512 KB of maps; every practical (T, block) pairing for this
#: framework sits far below it (65,536 tokens at 1024² → 2,080 tiles;
#: 128² blocks stay under the cap to T = 46k).
_TRI_TILE_CAP = 65_536


def _tri_tile_count(nq: int, nk: int, block_q: int, block_k: int,
                    window: Optional[int] = None,
                    order: str = "row") -> int:
    """Live-tile count of the causal triangle, or of `window`'s band, in
    the order walked (the triangle's two orders are equal but for a
    column of padding's dead tile; a band's differ at its edges)."""
    first, last = _band_edges(nq, nk, block_q, block_k, order, window)
    return int((last - first + 1).sum())


def _repeat_kv(q, k, v):
    """k and v with fewer heads than q (grouped-query attention), each
    repeated over its group of query heads; the gradient sums back over
    the group through the repeat.  Equal heads pass through untouched."""
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv == H:
        return k, v
    if H % Hkv or v.shape[2] != Hkv:
        raise ValueError(f"{H} query heads do not group over {Hkv} "
                         f"key/{v.shape[2]} value heads")
    return jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)


def attention_reference(q, k, v, causal: bool = True,
                        q_offset: int = 0, k_offset: int = 0,
                        scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Plain softmax attention. q: [B, T, H, D], k,v: [B, T, Hkv, D]
    with Hkv dividing H → [B, Tq, H, D].

    q_offset/k_offset give the global positions of local blocks so the
    causal mask stays correct under sequence sharding.  `scale`
    multiplies the scores; left `None` it is 1/√D.  `window` (causal
    only): a query meets the last `window` keys, its own position among
    them — `t − window < j ≤ t`.
    """
    k, v = _repeat_kv(q, k, v)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])[:, None]
        kpos = k_offset + jnp.arange(k.shape[1])[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_update(o, m, l, q, k_blk, v_blk, scale,
                     mask: Optional[jnp.ndarray] = None):
    """One online-softmax accumulation against a KV block.

    o: [B, Tq, H, D] running (unnormalized) output
    m: [B, H, Tq] running rowmax, l: [B, H, Tq] running denominator
    mask: [Tq, Tk] boolean (True = attend), already global-position-aware.
    Returns updated (o, m, l).  Final output is o / l[..., None].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new == NEG_INF): exp(NEG_INF - NEG_INF)=1
    # would pollute l; clamp the correction to 0 there.
    alive = m_new > NEG_INF / 2
    corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
    p = jnp.where(alive[..., None], jnp.exp(s - m_new[..., None]), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * jnp.transpose(corr, (0, 2, 1))[..., None] + \
        jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)
    return o_new, m_new, l_new


def finalize_blockwise(o, l):
    """Normalize accumulated output; fully-masked rows come out zero."""
    denom = jnp.transpose(jnp.where(l == 0.0, 1.0, l), (0, 2, 1))[..., None]
    return o / denom


# ------------------------------------------------------------------- geometry
#: the kernels the rule knows, by the suffix of their trace names: a
#: backward is `bwd_fused` alone where its dQ column fits, else the pair
KERNELS = ("fwd", "bwd_dkv", "bwd_dq", "bwd_fused")
#: those whose grid walks kv blocks outer, q blocks inner
_KV_OUTER = ("bwd_dkv", "bwd_fused")


@dataclasses.dataclass(frozen=True)
class FlashGeometry:
    """What one grid step of one flash kernel does, and the grid that
    follows from it: `heads` neighbouring heads of one batch row a step
    (a `heads·D`-lane column block of `[B, T, H·D]`), one
    [block_q, block_k] tile of scores each."""
    block_q: int
    block_k: int
    heads: int
    t_q: int          # query length padded to block_q
    t_k: int          # key length padded to block_k
    tri: bool         # triangular grid (live causal tiles only) or dense
    tiles: int        # grid tiles a head (the live ones when `tri`)
    grid_steps: int   # grid steps a call: B·H / heads × tiles
    # the keys a query meets, itself among them; None: its whole past —
    # with `tri`, the live tiles are a band's and not the triangle's
    window: Optional[int] = None


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _geometry(T: int, bh: int, causal: bool, block_q: int, block_k: int,
              heads: int, window: Optional[int] = None,
              kv_outer: bool = False) -> FlashGeometry:
    """The grid that `(block_q, block_k, heads)` names at length T; a
    band's live tiles in the order the kernel walks them (`kv_outer`:
    dK/dV's columns)."""
    t_q, t_k = _round_up(T, block_q), _round_up(T, block_k)
    nq, nk = t_q // block_q, t_k // block_k
    live = _tri_tile_count(
        nq, nk, block_q, block_k, window,
        "col" if kv_outer and window is not None else "row") \
        if causal else nq * nk
    # past the cap the scalar-prefetch maps outgrow SMEM-class storage:
    # the dense grid has O(1) metadata and skips dead tiles by pl.when
    tri = causal and live <= _TRI_TILE_CAP
    tiles = live if tri else nq * nk
    return FlashGeometry(block_q, block_k, heads, t_q, t_k, tri, tiles,
                         bh // heads * tiles, window)


#: scoped VMEM a kernel may take on the chip: Mosaic's default limit on
#: a v5e, which one call here raises — the fused backward's, whose dQ
#: column (a whole [t_q, G·D] float32 column of the step's heads, twice
#: with the pipeline's second buffer) stays in VMEM for a walk and is
#: larger than this beside the blocks at every long T; that call states
#: its own limit, its counted bytes and `_VMEM_MARGIN`, up to
#: `_FUSED_VMEM_CAP` of the chip's 128 MiB
_VMEM_BUDGET = 16 * 2 ** 20
_FUSED_VMEM_CAP = 64 * 2 ** 20
_VMEM_MARGIN = 8 * 2 ** 20
#: the float32 [block_q, block_k] arrays a tile's arithmetic holds at
#: once, counted from the kernels' bodies: the forward's scores and
#: probabilities; the backward's probabilities, dP and dS.  (The mask's
#: iotas and compare are consumed as they are made.)  Heads of one step
#: run one after the other and reuse them.  The fused backward counts
#: two more, the transposed p and dS its dV and dK products contract
#: over: under its larger cap the rule reaches 1,024² tiles at several
#: heads a step, where Mosaic's own allocation ran 8.6-12.1 MiB over a
#: count of three (compiled for a described v5e: 58.56 MiB against 50,
#: 72.14 against 60)
_TILE_TEMPS = {"fwd": 2, "bwd_dkv": 3, "bwd_dq": 3, "bwd_fused": 5}
#: the largest block the rule derives, and the cap on a block named for
#: the backward kernels: at 2048² the temporaries alone are 32-48 MiB
_MAX_BLOCK = 1024
#: the most heads one grid step handles where the lanes leave a choice:
#: the loop over a step's heads is unrolled, and `_COST_US` was fitted
#: up to here
_MAX_HEADS = 8
#: what a call costs, in µs on a v5e: a grid step (DMA set-up, index
#: maps, pl.when bookkeeping); a 128×128 unit of tile area (the
#: products and the elementwise softmax work); a 128-row chunk of the
#: q side a tile (the forward's m/l/acc rescale, the backward's q, dO,
#: lse and delta streaming in); a 128-key chunk of the kv side a tile.
#: Fitted to PR 25's sweep of each kernel alone (on folded [B·H, T, D]
#: copies then; PR 27's sweep of the column blocks kept the constants:
#: PERF.md §6) over blocks
#: {128..1024}² × heads {1..8} at three shapes — (B·H, T, D) = (64,
#: 1024, 64) and (256, 256, 64) in float32, (2, 65536, 128) in bfloat16
#: — one set for all three: the kernels are bound by stepping and
#: vector work, not by the products, so neither dtype nor head width
#: moves the constants far.  PERF.md §6 (PR 25) has the table.
_COST_US = {"fwd": (0.25, 0.030, 0.28, 0.025),
            "bwd_dkv": (0.31, 0.040, 0.21, 0.075),
            "bwd_dq": (0.26, 0.043, 0.078, 0.062),
            # PR 49's sweep of the fused call alone, 116 geometries at
            # the cells' shapes (PERF.md §6): median error 5%, a head of
            # 192 under-predicted by a quarter (no term knows D)
            "bwd_fused": (0.44, 0.113, 0.077, 0.028)}
#: the largest tile the fused backward derives, in scores: at 1,024² its
#: five temporaries are 20 MiB, the same sweep measured such tiles no
#: faster a head than 512 × 1,024 (65.85 against 66.29 ms at T 16,384,
#: 5.00 against 5.02 at 8,192) — a cost no term of `_COST_US` has — and
#: they leave the cap no room for the second head a step, which is
#: worth 5-10%
_FUSED_MAX_TILE = 512 * 1024


def _vmem_cap(kernel: str) -> int:
    """The scoped VMEM the rule may count for a call of `kernel`."""
    return _FUSED_VMEM_CAP if kernel == "bwd_fused" else _VMEM_BUDGET


def _column_bytes(t_q: int, heads: int, D: int) -> int:
    """The fused backward's dQ column: `t_q` rows of the step's heads'
    lanes in float32, one buffer of it."""
    return t_q * _round_up(heads * D, 128) * 4


def _vmem_bytes(kernel: str, block_q: int, block_k: int, heads: int, D: int,
                itemsize: int, Dv: Optional[int] = None, t_q: int = 0) -> int:
    """Scoped VMEM one grid step needs, counted: every operand's block
    twice (the pipeline double-buffers), the float32 scratch, and the
    tile's temporaries.  A block of q, k or their gradients is `heads·D`
    lanes wide, one of v, out or their gradients `heads·Dv` (`Dv` left
    `None` is D) — the step's heads side by side, padded to 128 only
    where the whole width is narrower — and a [block_q, 1] row statistic
    pads to 128 lanes a head, so one head's is as wide as a 128-lane q
    block.  The fused backward's blocks are dK/dV's, and its dQ output
    is the whole column of `t_q` rows (`_column_bytes`, twice)."""
    lanes = _round_up(heads * D, 128)
    v_lanes = _round_up(heads * (D if Dv is None else Dv), 128)
    q_blk, k_blk = block_q * lanes, block_k * lanes
    o_blk, v_blk = block_q * v_lanes, block_k * v_lanes
    stat = heads * block_q * 128 * 4
    column = 2 * _column_bytes(t_q, heads, D) if kernel == "bwd_fused" else 0
    if kernel == "fwd":
        piped = (q_blk + o_blk + k_blk + v_blk) * itemsize + stat  # .. lse
        scratch = o_blk * 4 + 2 * stat                      # acc; m, l
    elif kernel in _KV_OUTER:
        # q, dO; k, v, dk, dv
        piped = (q_blk + o_blk + 2 * k_blk + 2 * v_blk) * itemsize + 2 * stat
        scratch = (k_blk + v_blk) * 4                       # dk, dv
    else:
        # q, dO, dq; k, v
        piped = (2 * q_blk + o_blk + k_blk + v_blk) * itemsize + 2 * stat
        scratch = q_blk * 4                                 # dq
    return 2 * piped + column + scratch \
        + _TILE_TEMPS[kernel] * block_q * block_k * 4


def _cost_us(kernel: str, geom: FlashGeometry, bh: int) -> float:
    step, unit, q_chunk, k_chunk = _COST_US[kernel]
    nbq, nbk = geom.block_q // 128, geom.block_k // 128
    return geom.grid_steps * step + bh * geom.tiles * (
        nbq * nbk * unit + nbq * q_chunk + nbk * k_chunk)


def _head_groups(H: int, D: int, Dv: Optional[int] = None) -> list:
    """The heads a grid step may take, fewest first: `G` neighbouring
    heads are one column block of `[B, T, H·D]` (and one of v's and
    out's `[B, T, H·Dv]`), so `G` divides H and `G·D` and `G·Dv` are
    whole 128-lane columns — or `G = H`, the whole width, which any
    shape may take.  At D = 64 that is 2, 4, 8 …; at D = 128, 1, 2, …;
    at D = 192 beside Dv = 128, 2, 4, ….  Past `_MAX_HEADS` only the
    fewest is left."""
    Dv = D if Dv is None else Dv
    groups = [g for g in range(1, H + 1)
              if H % g == 0 and ((g * D % 128 == 0 and g * Dv % 128 == 0)
                                 or g == H)]
    return [g for g in groups if g <= _MAX_HEADS] or groups[:1]


def flash_geometry(kernel: str, T: int, D: int, itemsize: int, B: int,
                   H: int, causal: bool, block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   Dv: Optional[int] = None,
                   window: Optional[int] = None) -> Optional[FlashGeometry]:
    """The step geometry of one flash kernel (`fwd`, `bwd_dkv`,
    `bwd_dq`, `bwd_fused`), from what the code can see at trace time —
    the one place that knows tile sizes, and which form a backward
    takes: one backward kernel, two where the dQ column does not fit.

    A block left `None` is derived: of the multiples of 128 that divide
    T padded to 128 (up to `_MAX_BLOCK`) and the heads a step that the
    lanes allow (`_head_groups`), the geometry whose counted VMEM fits
    the kernel's cap (`_VMEM_BUDGET`; the fused backward's
    `_FUSED_VMEM_CAP`, its tile no larger than `_FUSED_MAX_TILE`) and
    whose modelled time (`_COST_US`) is least.
    Larger tiles save grid steps and per-chunk state and compute more
    of the causal triangle's dead area (at T = 1,024: 128² 36 units of
    128² in 36 steps a head, 512² 48 in 3, 1024² 64 in 1); more heads a
    step save steps with no such waste, which is what short windows
    need.  A block that is named is honoured as it stands — the fewest
    heads a step the lanes allow, the backward kernels capped at
    `_MAX_BLOCK` — and only the other is derived.  `D` is the width of
    a query and key head, `Dv` that of a value head where it differs
    (latent attention: 192 beside 128).  Under `window` (causal only; a
    window of T or more is none) the tiles counted are the band's, so
    the same model weighs a smaller tile's fuller band against its
    steps.

    `bwd_fused` is dK/dV's grid with dQ's whole column of the step's
    heads resident beside the blocks — `t_q × G·D` float32, twice: 8 MiB
    at T = 16,384 and one head of 128, 12.6 MB at 8,192 and two of 192
    — so five products a tile where the pair makes seven.  Where no
    geometry's count fits its cap (T = 65,536 at a head of 128: 32 MiB,
    twice), named blocks or not, the answer is `None`: that backward
    takes the two kernels."""
    if window is not None and (not causal or window >= T):
        window = None
    named = block_q is not None, block_k is not None
    cap = float("inf") if kernel == "fwd" else _MAX_BLOCK
    n = _round_up(T, 128) // 128
    derived = [128 * m for m in range(1, _MAX_BLOCK // 128 + 1) if n % m == 0]
    groups, bh = _head_groups(H, D, Dv), B * H
    geoms = [_geometry(T, bh, causal, bq, bk, h, window, kernel in _KV_OUTER)
             for bq in ([min(block_q, cap)] if named[0] else derived)
             for bk in ([min(block_k, cap)] if named[1] else derived)
             if kernel != "bwd_fused" or all(named)
             or bq * bk <= _FUSED_MAX_TILE
             for h in (groups[:1] if any(named) else groups)]
    fits = [g for g in geoms if _vmem_bytes(
        kernel, g.block_q, g.block_k, g.heads, D, itemsize, Dv, g.t_q)
        <= _vmem_cap(kernel)]
    if kernel == "bwd_fused":
        # a column that does not fit is no smaller under other blocks
        geoms = fits
    elif not all(named):
        # the first is the smallest: what is left when nothing fits, for
        # the compiler to refuse
        geoms = fits or geoms[:1]
    return min(geoms, key=lambda g: _cost_us(kernel, g, bh), default=None)


# --------------------------------------------------------------------- pallas
def _tile_positions(i, j, block_q: int, block_k: int):
    """(qi, kj): global row and column positions of tile (i, j)."""
    qi = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + i * block_q
    kj = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1) + j * block_k
    return qi, kj


@functools.partial(jax.jit, static_argnames=("scale",))
def _fwd_tile(q, k, v, mask, m, l, acc, *, scale: float):
    """One head's tile of the forward, as values: the online-softmax
    update of (m, l, acc) against one [block_k, D] tile of K/V.  Jitted
    so that its body is traced once a shape and not once a head, a
    layer and a kernel: the nested call is inlined when the kernel
    lowers.

    Every product takes its operands at the INPUT dtype and accumulates
    in float32; the scale is applied to the f32 scores afterwards.  P is
    computed in f32 (softmax stability) then cast to the input dtype for
    P·V — the standard flash-attention trade for bf16 inputs (exact QK
    products, a P rounding below the bf16 output's own); for float32
    inputs the cast is the identity and how Mosaic runs a float32
    product is its default (PERF.md §7)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alive = m_new > NEG_INF / 2
    corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
    p = jnp.where(alive, jnp.exp(s - m_new), 0.0)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _head_columns(q_ref, v_ref, stat_ref) -> list:
    """(g, columns of q and k, columns of v and out) of each head of a
    step: the row statistics' block leads with the step's heads, an
    operand's `[1, block, G·D]` column block holds them side by side, D
    lanes each (v's and out's Dv lanes each)."""
    G = stat_ref.shape[0]
    D, Dv = q_ref.shape[2] // G, v_ref.shape[2] // G
    return [(g, slice(g * D, (g + 1) * D), slice(g * Dv, (g + 1) * Dv))
            for g in range(G)]


def _fwd_step(i, j, first, last, live, q_ref, k_ref, v_ref, o_ref, lse_ref,
              acc, m_s, l_s, *, scale: float, causal: bool, block_q: int,
              block_k: int, window: Optional[int] = None):
    """One grid step of the forward: tile (i, j) of every head in the
    column block.  K/V stream through VMEM one [block_k, G·D] tile at a
    time (O(T) VMEM, long-context safe); the online-softmax state lives
    in scratch that persists across the kv tiles of one q block.
    `first` and `last` say whether (i, j) opens or closes its q row,
    `live` (dense causal grid only) whether the tile holds any past
    key."""
    from jax.experimental import pallas as pl

    heads = _head_columns(q_ref, v_ref, lse_ref)

    @pl.when(first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def compute():
        # the elementwise causal mask runs on EVERY tile even though only
        # diagonal-straddling tiles need it: branch-specializing it
        # behind a lax.cond was measured slower and, in the backward,
        # the duplicated branch temporaries overflowed scoped VMEM at
        # 1024² tiles (ARCHITECTURE §5).  One mask a step, shared by
        # the step's heads.
        mask = None
        if causal:
            qi, kj = _tile_positions(i, j, block_q, block_k)
            mask = qi >= kj
            if window is not None:
                # the band's far edge: a key `window` or more back
                mask = mask & (kj > qi - window)
        for g, cols, vcols in heads:
            m_s[g], l_s[g], acc[:, vcols] = _fwd_tile(
                q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, vcols],
                mask, m_s[g], l_s[g], acc[:, vcols], scale=scale)

    if live is None:
        compute()
    else:
        # whole KV block strictly in the future of this q block → skip
        pl.when(live)(compute)

    @pl.when(last)
    def _emit():
        l = l_s[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        for g, _, vcols in heads:
            o_ref[0, :, vcols] = (acc[:, vcols]
                                  / safe_l[g]).astype(o_ref.dtype)
        # log-sum-exp per query row (needed by the custom-VJP backward)
        lse_ref[:] = jnp.where(l == 0.0, NEG_INF, m_s[:] + jnp.log(safe_l))


def _bwd_mask(i, j, *, causal, block_q, block_k, t_real, window=None):
    qi, kj = _tile_positions(i, j, block_q, block_k)
    mask = kj < t_real
    if causal:
        mask = mask & (qi >= kj)
    if window is not None:
        mask = mask & (kj > qi - window)
    return mask


def _bwd_tile(q, k, v, do, lse, delta, mask, scale):
    """Shared recompute for both backward kernels, one head's tile:
    returns (p, ds) in float32.

    Dtype policy mirrors the forward: the score and dP products take
    their operands at the input dtype and accumulate in float32; p/ds
    stay f32 — they are exp-of-f32 quantities the gradient tolerances
    pin.  The mask runs on every tile (see the forward's note)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds


@functools.partial(jax.jit, static_argnames=("scale",))
def _dkv_tile(q, k, v, do, lse, delta, mask, dk, dv, *, scale: float):
    """One head's tile of dK/dV, as values (jitted as `_fwd_tile` is):
    p/ds cast to the input dtype, f32 accumulation."""
    p, ds = _bwd_tile(q, k, v, do, lse, delta, mask, scale)
    dv = dv + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk = dk + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dk, dv


@functools.partial(jax.jit, static_argnames=("scale",))
def _dq_tile(q, k, v, do, lse, delta, mask, dq, *, scale: float):
    """One head's tile of dQ, as values."""
    _, ds = _bwd_tile(q, k, v, do, lse, delta, mask, scale)
    return dq + jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale",))
def _fused_tile(q, k, v, do, lse, delta, mask, dq, dk, dv, *, scale: float):
    """One head's tile of the one-kernel backward, as values: p and ds
    made once, then dK/dV's two products and dQ's — five a tile."""
    p, ds = _bwd_tile(q, k, v, do, lse, delta, mask, scale)
    ds = ds.astype(q.dtype)
    dv = dv + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk = dk + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq = dq + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dq, dk, dv


def _dkv_step(i, j, first, last, live, q_ref, do_ref, lse_ref, delta_ref,
              k_ref, v_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
              block_q, block_k, t_real, window=None):
    """One grid step of dK/dV: for one kv block, the q blocks stream
    through VMEM accumulating dk/dv in scratch; p never touches HBM.
    On the triangular grid a column entirely in the future of every
    query keeps one dead diagonal tile whose mask zeroes p/ds, so its
    dk/dv block is still zero-written (see _causal_tiles)."""
    from jax.experimental import pallas as pl

    heads = _head_columns(q_ref, v_ref, lse_ref)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        mask = _bwd_mask(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, t_real=t_real, window=window)
        for g, cols, vcols in heads:
            dk_acc[:, cols], dv_acc[:, vcols] = _dkv_tile(
                q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, vcols],
                do_ref[0, :, vcols], lse_ref[g], delta_ref[g], mask,
                dk_acc[:, cols], dv_acc[:, vcols], scale=scale)

    if live is None:
        compute()
    else:
        # q blocks strictly before this kv block contribute nothing
        pl.when(live)(compute)

    @pl.when(last)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_step(i, j, first, last, live, q_ref, do_ref, lse_ref, delta_ref,
             k_ref, v_ref, dq_ref, dq_acc, *, scale, causal, block_q,
             block_k, t_real, window=None):
    """One grid step of dQ: one q block accumulates over its (causally
    relevant) kv blocks."""
    from jax.experimental import pallas as pl

    heads = _head_columns(q_ref, v_ref, lse_ref)

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        mask = _bwd_mask(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, t_real=t_real, window=window)
        for g, cols, vcols in heads:
            dq_acc[:, cols] = _dq_tile(
                q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, vcols],
                do_ref[0, :, vcols], lse_ref[g], delta_ref[g], mask,
                dq_acc[:, cols], scale=scale)

    if live is None:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(last)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _fused_step(i, j, first, last, live, q_ref, do_ref, lse_ref, delta_ref,
                k_ref, v_ref, dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, *,
                scale, causal, block_q, block_k, t_real, window=None):
    """One grid step of the one-kernel backward, on dK/dV's grid: dk and
    dv accumulate in scratch along a kv column as in `_dkv_step`, and dQ
    in its output block — the WHOLE float32 q column of the step's
    heads, whose block index does not move along the walk of one (batch
    row, head group), so it stays in VMEM from the walk's first tile,
    which zeroes it, to its last, after which it is written back once.
    Columns come in rising j, so a q row's sums over kv blocks run in
    `_dq_step`'s order."""
    from jax.experimental import pallas as pl

    heads = _head_columns(q_ref, v_ref, lse_ref)

    # the walk's first tile on either grid: the first of kv column 0
    @pl.when(first & (j == 0))
    def _zero():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        mask = _bwd_mask(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, t_real=t_real, window=window)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        for g, cols, vcols in heads:
            dq_ref[0, rows, cols], dk_acc[:, cols], dv_acc[:, vcols] = \
                _fused_tile(
                    q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, vcols],
                    do_ref[0, :, vcols], lse_ref[g], delta_ref[g], mask,
                    dq_ref[0, rows, cols], dk_acc[:, cols],
                    dv_acc[:, vcols], scale=scale)

    if live is None:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(last)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dense_kernel(*refs, step, kv_outer: bool, causal: bool, block_q: int,
                  block_k: int, window: Optional[int] = None, **static):
    """A step function on the dense grid (B, H/heads, outer, inner): the
    inner axis iterates sequentially on-core — kv blocks for the
    forward and dQ, q blocks for dK/dV (`kv_outer`)."""
    from jax.experimental import pallas as pl

    outer, inner = pl.program_id(2), pl.program_id(3)
    i, j = (inner, outer) if kv_outer else (outer, inner)
    live = j * block_k <= i * block_q + (block_q - 1) if causal else None
    if window is not None:
        # a band past the cap on the maps: the tiles behind it are
        # skipped as the future's are
        live = live & (j * block_k + block_k - 1 > i * block_q - window)
    step(i, j, inner == 0, inner == pl.num_programs(3) - 1, live, *refs,
         causal=causal, block_q=block_q, block_k=block_k, window=window,
         **static)


def _tri_kernel(im_ref, jm_ref, *refs, step, kv_outer: bool, block_q: int,
                block_k: int, nq: int, nk: int,
                window: Optional[int] = None, **static):
    """A step function on the TRIANGULAR grid (B, H/heads, tiles): the
    last axis walks only the live lower-triangle tiles (row-major for
    the forward and dQ, column-major for dK/dV), the (i, j) tile
    coordinates arriving via scalar prefetch.  Strictly-future tiles do
    not exist, so they pay neither their DMA nor a grid step."""
    from jax.experimental import pallas as pl

    t = pl.program_id(2)
    i = im_ref[t]
    j = jm_ref[t]
    if kv_outer:
        # first live q block of this kv column … the last q block
        first = i == jnp.minimum(nq - 1, (j * block_k) // block_q)
        last = i == nq - 1
    else:
        # first kv block … the diagonal block of this q row
        first = j == 0
        last = j == jnp.minimum(nk - 1,
                                (i * block_q + block_q - 1) // block_k)
    # a band's far edge (`_band_edges`, on the scalars of this step): the
    # last q block that still meets this kv column's last key, the first
    # kv block that holds a key the row's first query still meets
    if window is not None and kv_outer:
        last = i == jnp.minimum(
            nq - 1, (j * block_k + block_k + window - 2) // block_q)
    elif window is not None:
        first = j == jnp.minimum(
            jnp.minimum(nk - 1, (i * block_q + block_q - 1) // block_k),
            jnp.maximum(i * block_q - window + 1, 0) // block_k)
    step(i, j, first, last, None, *refs, causal=True, block_q=block_q,
         block_k=block_k, window=window, **static)


def mask_area(T: int, causal: bool, window: Optional[int] = None) -> int:
    """The scores a head's mask lets through at length T: all T², the
    causal triangle's T (T + 1) / 2, or a band's — a query meets
    `min(t + 1, window)` keys."""
    if not causal:
        return T * T
    w = T if window is None else min(window, T)
    return w * (w + 1) // 2 + (T - w) * w


def _flash_grid(kernel: str, geom: FlashGeometry, step, *, B: int, H: int,
                D: int, Dv: int, T: int, kv_outer: bool, causal: bool,
                ins: str, outs: str, scratch, copies: int,
                vmem_limit: Optional[int] = None, **static):
    """(kernel function, grid spec, compiler params, prefetch operands)
    of one flash kernel on the grid `geom` names — and the record of
    that geometry (`iotml_flash_*{kernel}`, at trace time).  `ins` and
    `outs` give each operand's side and width: Q/K a [1, block, G·D]
    column block of a `[B, T, H·D]` array along the q or the kv axis —
    batch row b, the c-th group of G heads — O/V the same of a
    `[B, T, H·Dv]` array (out and dO along q; v and dv along kv), and q
    the [G, block_q, 1] row statistics of the same heads in a
    `[B·H, t_q, 1]` array; C is the whole `[1, t_q, G·D]` column of
    those heads, the same block at every tile of a walk.  `vmem_limit`
    is stated by the one call whose blocks outgrow Mosaic's default."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, bq, bk = geom.heads, geom.block_q, geom.block_k
    nq, nk, nc = geom.t_q // bq, geom.t_k // bk, H // G
    if geom.tri:
        im, jm = _causal_tiles(nq, nk, bq, bk, "col" if kv_outer else "row",
                               geom.window)
        prefetch = (jnp.asarray(im), jnp.asarray(jm))
        grid = (B, nc, len(im))
        qtile = lambda t, im, jm: im[t]  # noqa: E731
        ktile = lambda t, im, jm: jm[t]  # noqa: E731
        fn = functools.partial(_tri_kernel, step=step, kv_outer=kv_outer,
                               block_q=bq, block_k=bk, nq=nq, nk=nk,
                               window=geom.window, **static)
    else:
        prefetch = ()
        # the grid's last two axes arrive as (outer, inner)
        grid = (B, nc, nk, nq) if kv_outer else (B, nc, nq, nk)
        qtile = lambda o, n: n if kv_outer else o  # noqa: E731
        ktile = lambda o, n: o if kv_outer else n  # noqa: E731
        fn = functools.partial(_dense_kernel, step=step, kv_outer=kv_outer,
                               causal=causal, block_q=bq, block_k=bk,
                               window=geom.window, **static)
    assert math.prod(grid) == geom.grid_steps, (grid, geom)
    _record_geometry(kernel, geom, lanes=G * D, value_lanes=G * Dv,
                     copies=copies, causal=causal, T=T)
    q_side = lambda b, c, *t: (b, qtile(*t), c)  # noqa: E731
    kv_side = lambda b, c, *t: (b, ktile(*t), c)  # noqa: E731
    blocks = {
        "Q": pl.BlockSpec((1, bq, G * D), q_side),
        "O": pl.BlockSpec((1, bq, G * Dv), q_side),
        "q": pl.BlockSpec((G, bq, 1),
                          lambda b, c, *t: (b * nc + c, qtile(*t), 0)),
        "K": pl.BlockSpec((1, bk, G * D), kv_side),
        "V": pl.BlockSpec((1, bk, G * Dv), kv_side),
        "C": pl.BlockSpec((1, geom.t_q, G * D), lambda b, c, *t: (b, 0, c))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid,
        in_specs=[blocks[c] for c in ins],
        out_specs=[blocks[c] for c in outs],
        scratch_shapes=scratch)
    params = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel") + ("arbitrary",) * (len(grid) - 2),
        vmem_limit_bytes=vmem_limit)
    return fn, grid_spec, params, prefetch


def _record_geometry(kernel: str, geom: FlashGeometry, *, lanes: int,
                     value_lanes: int, copies: int, causal: bool,
                     T: int) -> None:
    """Say what engaged: Python at trace time, once a shape and
    compilation, no cost in the step.  The last newly traced call of
    each kernel stands — and, by kernel AND mask (`dense`, `causal`,
    `band`), the last of each: one program may hold causal calls beside
    band calls, and says both."""
    from ..obs import metrics as obs_metrics

    mask = "band" if geom.window is not None else \
        "causal" if causal else "dense"
    by_mask = dict(kernel=kernel, kind=mask)
    obs_metrics.flash_mask_window.set(geom.window or 0, **by_mask)
    obs_metrics.flash_mask_tiles.set(geom.tiles, **by_mask)
    obs_metrics.flash_mask_walked_area.set(
        geom.tiles * geom.block_q * geom.block_k, **by_mask)
    obs_metrics.flash_mask_live_area.set(
        mask_area(T, causal, geom.window), **by_mask)

    obs_metrics.flash_grid_steps.set(geom.grid_steps, kernel=kernel)
    obs_metrics.flash_block_q.set(geom.block_q, kernel=kernel)
    obs_metrics.flash_block_k.set(geom.block_k, kernel=kernel)
    obs_metrics.flash_heads_per_step.set(geom.heads, kernel=kernel)
    obs_metrics.flash_lanes_per_step.set(lanes, kernel=kernel)
    obs_metrics.flash_value_lanes_per_step.set(value_lanes, kernel=kernel)
    obs_metrics.flash_operand_copies.set(copies, kernel=kernel)


def _pad_t(x, t_pad: int, pad_value=0.0):
    T = x.shape[1]
    if t_pad == T:
        return x
    return jnp.pad(x, [(0, 0), (0, t_pad - T), (0, 0)],
                   constant_values=pad_value)


def _operand_copies(T: int, geom: FlashGeometry, q_side: int,
                    repeated: int) -> int:
    """How many of a call's operands were copied ahead of the kernel:
    the `q_side` operands of the q axis where T pads to `t_q`; k and v
    where T pads to `t_k` or the caller repeated them (`repeated`)."""
    return q_side * (geom.t_q != T) + max(repeated, 2 * (geom.t_k != T))


def _flash_fwd(q, k, v, H: int, causal: bool, geom: FlashGeometry,
               interpret: bool, scale: float, repeated: int = 0):
    """The forward of unpadded operands in the projections' layout
    (q and k [B, T, H·D], v [B, T, H·Dv]) on `geom`: (out
    [B, t_q, H·Dv], lse [B·H, t_q, 1]).
    `repeated` says how many of them the caller copied ahead of this
    (a repeated k or v), for `iotml_flash_operand_copies`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, HD = q.shape
    D, Dv = HD // H, v.shape[2] // H
    G, bq, Tq, Tk = geom.heads, geom.block_q, geom.t_q, geom.t_k
    if not causal and Tk != T:
        # padded keys are only excluded by the causal mask; non-causal
        # callers must supply block-multiple sequence lengths
        raise ValueError(
            f"non-causal flash attention needs T % {geom.block_k} == 0")
    fn, grid_spec, params, prefetch = _flash_grid(
        "fwd", geom, _fwd_step, B=B, H=H, D=D, Dv=Dv, T=T, kv_outer=False,
        causal=causal, ins="QKV", outs="Oq",
        scratch=[pltpu.VMEM((bq, G * Dv), jnp.float32),
                 pltpu.VMEM((G, bq, 1), jnp.float32),
                 pltpu.VMEM((G, bq, 1), jnp.float32)],
        copies=_operand_copies(T, geom, 1, repeated), scale=scale)
    # padded keys never win the max: values 0, and the causal mask
    # (global positions) excludes them for every real query
    return pl.pallas_call(
        fn, name=FWD_KERNEL, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Tq, H * Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, Tq, 1), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(*prefetch, _pad_t(q, Tq), _pad_t(k, Tk), _pad_t(v, Tk))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_forward(q, k, v, causal: bool, block_q: Optional[int],
                   block_k: Optional[int], interpret: bool, scale: float,
                   repeated: int, window: Optional[int] = None):
    """Run the Pallas kernel; returns (out [B,T,H,Dv], lse [B,H,T]).
    Jitted, as `_flash_backward` is, so that a model of many equal
    layers traces and lowers the kernels once a shape and not once a
    layer (`_record_geometry` runs then, once)."""
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    geom = flash_geometry("fwd", T, D, q.dtype.itemsize, B, H, causal,
                          block_q, block_k, Dv, window)
    out, lse = _flash_fwd(*(x.reshape(B, T, -1) for x in (q, k, v)), H,
                          causal, geom, interpret, scale, repeated)
    return (out[:, :T].reshape(B, T, H, Dv),
            lse.reshape(B, H, -1)[:, :, :T])


def _bwd_operands(q, do, lse, delta, k, v, geom: FlashGeometry,
                  repeated: int):
    """The backward's operands padded to `geom`, and how many of the
    six were copied on the way (`repeated` of them by the caller).
    Padded q rows take a +BIG lse → p = exp(s - BIG) = 0, so they
    contribute nothing to dk/dv and their dq rows are sliced off;
    padded keys are masked by `t_real`."""
    Tq, Tk = geom.t_q, geom.t_k
    return (_pad_t(q, Tq), _pad_t(do, Tq), _pad_t(lse, Tq, 1e30),
            _pad_t(delta, Tq), _pad_t(k, Tk), _pad_t(v, Tk)), \
        _operand_copies(q.shape[1], geom, 4, repeated)


def _flash_bwd_dkv(q, do, lse, delta, k, v, H: int, causal: bool,
                   geom: FlashGeometry, interpret: bool, scale: float,
                   repeated: int = 0):
    """dK/dV of unpadded operands (q and k [B, T, H·D], dO and v
    [B, T, H·Dv]; lse and delta [B·H, T, 1]) on `geom`:
    ([B, t_k, H·D], [B, t_k, H·Dv])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, HD = q.shape
    D, Dv = HD // H, v.shape[2] // H
    G, bk, Tk = geom.heads, geom.block_k, geom.t_k
    operands, copies = _bwd_operands(q, do, lse, delta, k, v, geom, repeated)
    fn, grid_spec, params, prefetch = _flash_grid(
        "bwd_dkv", geom, _dkv_step, B=B, H=H, D=D, Dv=Dv, T=T,
        kv_outer=True,
        causal=causal, ins="QOqqKV", outs="KV",
        scratch=[pltpu.VMEM((bk, G * D), jnp.float32),
                 pltpu.VMEM((bk, G * Dv), jnp.float32)],
        copies=copies, scale=scale, t_real=T)
    return pl.pallas_call(
        fn, name=BWD_DKV_KERNEL, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Tk, HD), k.dtype),
                   jax.ShapeDtypeStruct((B, Tk, H * Dv), v.dtype)],
        compiler_params=params, interpret=interpret,
    )(*prefetch, *operands)


def _flash_bwd_dq(q, do, lse, delta, k, v, H: int, causal: bool,
                  geom: FlashGeometry, interpret: bool, scale: float,
                  repeated: int = 0):
    """dQ of the same operands on `geom`: [B, t_q, H·D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, HD = q.shape
    D, Dv = HD // H, v.shape[2] // H
    G, bq, Tq = geom.heads, geom.block_q, geom.t_q
    operands, copies = _bwd_operands(q, do, lse, delta, k, v, geom, repeated)
    fn, grid_spec, params, prefetch = _flash_grid(
        "bwd_dq", geom, _dq_step, B=B, H=H, D=D, Dv=Dv, T=T, kv_outer=False,
        causal=causal, ins="QOqqKV", outs="Q",
        scratch=[pltpu.VMEM((bq, G * D), jnp.float32)],
        copies=copies, scale=scale, t_real=T)
    (dq,) = pl.pallas_call(
        fn, name=BWD_DQ_KERNEL, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Tq, HD), q.dtype)],
        compiler_params=params, interpret=interpret,
    )(*prefetch, *operands)
    return dq


def _flash_bwd_fused(q, do, lse, delta, k, v, H: int, causal: bool,
                     geom: FlashGeometry, interpret: bool, scale: float,
                     repeated: int = 0):
    """dK, dV and dQ of the same operands in ONE call on `geom`, dK/dV's
    grid: ([B, t_k, H·D], [B, t_k, H·Dv], float32 [B, t_q, H·D]).  The
    call states its scoped VMEM: what `_vmem_bytes` counts, the dQ
    column in the count, and `_VMEM_MARGIN`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, HD = q.shape
    D, Dv = HD // H, v.shape[2] // H
    G, bk, Tq, Tk = geom.heads, geom.block_k, geom.t_q, geom.t_k
    operands, copies = _bwd_operands(q, do, lse, delta, k, v, geom, repeated)
    fn, grid_spec, params, prefetch = _flash_grid(
        "bwd_fused", geom, _fused_step, B=B, H=H, D=D, Dv=Dv, T=T,
        kv_outer=True, causal=causal, ins="QOqqKV", outs="KVC",
        scratch=[pltpu.VMEM((bk, G * D), jnp.float32),
                 pltpu.VMEM((bk, G * Dv), jnp.float32)],
        copies=copies,
        vmem_limit=_VMEM_MARGIN + _vmem_bytes(
            "bwd_fused", geom.block_q, bk, G, D, q.dtype.itemsize, Dv, Tq),
        scale=scale, t_real=T)
    return pl.pallas_call(
        fn, name=BWD_FUSED_KERNEL, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Tk, HD), k.dtype),
                   jax.ShapeDtypeStruct((B, Tk, H * Dv), v.dtype),
                   jax.ShapeDtypeStruct((B, Tq, HD), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(*prefetch, *operands)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_backward(q, k, v, out, lse, do, causal: bool,
                    block_q: Optional[int], block_k: Optional[int],
                    interpret: bool, scale: float, repeated: int,
                    window: Optional[int] = None):
    """Pallas flash-attention backward — p and ds recomputed blockwise
    in VMEM, never materialized to HBM: one backward kernel, two where
    the dQ column does not fit.  The one (`iotml_flash_bwd_fused`) walks
    dK/dV's grid with dQ's float32 column of the step's heads resident
    (`t_q × G·D × 4` bytes, twice), makes scores, dP, the exponent, the
    mask and ds once a tile and five products from them; where
    `flash_geometry("bwd_fused", …)` finds no geometry under its cap,
    the standard two-kernel split runs as it always did (dkv sweeping q
    per kv block; dq sweeping kv per q block: seven products a tile
    pair), each kernel on its own geometry.  Which ran is read off the
    shape, and said: `iotml_flash_backward_fused`,
    `iotml_flash_bwd_column_bytes`."""
    from ..obs import metrics as obs_metrics

    B, T, H, D = q.shape
    # rowwise D_i = sum_d dO_i·O_i (softmax-jacobian diagonal term)
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       out.astype(jnp.float32))
    flat = lambda x: x.reshape(B, T, -1)  # noqa: E731
    operands = (flat(q), flat(do), lse.reshape(B * H, T, 1),
                delta.reshape(B * H, T, 1), flat(k), flat(v))
    geometry = lambda kernel: flash_geometry(  # noqa: E731
        kernel, T, D, q.dtype.itemsize, B, H, causal, block_q, block_k,
        v.shape[-1], window)
    fused = geometry("bwd_fused")
    obs_metrics.flash_backward_fused.set(fused is not None)
    obs_metrics.flash_bwd_column_bytes.set(
        _column_bytes(fused.t_q, fused.heads, D) if fused else 0)
    if fused is not None:
        dk, dv, dq = _flash_bwd_fused(*operands, H, causal, fused, interpret,
                                      scale, repeated)
        # the float32 column cast once, where XLA fuses it
        dq = dq.astype(q.dtype)
    else:
        dk, dv = _flash_bwd_dkv(*operands, H, causal, geometry("bwd_dkv"),
                                interpret, scale, repeated)
        dq = _flash_bwd_dq(*operands, H, causal, geometry("bwd_dq"),
                           interpret, scale, repeated)
    return tuple(x[:, :T].reshape(B, T, H, -1) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, block_q, block_k, interpret, scale, repeated,
           window):
    """`flash_attention` of equal heads: the differentiable core."""
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                            scale, repeated, window)
    return out


def _flash_fwd_rule(q, k, v, *static):
    """The kernel's two results go by name: a caller that recomputes its
    forward in the backward pass (`jax.checkpoint`) can keep them with a
    policy of names and spare the kernel's second run — q, k and v it
    makes again with its projections.  Outside any recomputation a name
    is the identity."""
    out, lse = _flash_forward(q, k, v, *static)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, scale, repeated,
                    window, res, do):
    return _flash_backward(*res, do, causal, block_q, block_k, interpret,
                           scale, repeated, window)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = False,
                    scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Pallas flash attention. q: [B, T, H, D], k: [B, T, Hkv, D],
    v: [B, T, Hkv, Dv] with Hkv dividing H → [B, T, H, Dv].  `Dv` may
    differ from `D` (latent attention: rotary features ride q and k
    only): v, out and their gradients are then column blocks of `G·Dv`
    lanes beside q's and k's of `G·D`, one G for both.

    The kernels index that layout in place: each operand and each result
    is `[B, T, H·D]` to them (a free reshape), a grid step's heads one
    128-lane-aligned column block of it, so nothing is transposed or
    copied on the way in or out — q, k, v as the projections wrote them,
    the gradients as the projections' backward reads them.

    `block_q`/`block_k` left `None` are derived from the shape, each
    kernel its own (`flash_geometry`), with as many heads a
    step as the lanes allow and the model of their cost prefers; a value
    given is honoured as it stands, with the fewest heads the lanes
    allow (`G·D` a multiple of 128, or all H).  T is padded to the
    block size internally
    (padding keys are masked out by the causal structure; non-causal
    callers must pass T multiple of the block).  `interpret=True` runs
    the same kernel on CPU for tests.  `scale` multiplies the scores;
    left `None` it is 1/√D.  Fewer key/value heads than query heads
    (grouped-query attention) are repeated over their groups ahead of
    the kernels, which see equal heads.  `window` (causal only): a query
    meets its last `window` keys, itself among them; the grids walk the
    band's tiles only, and a window of T or more is none — the causal
    call, built as it always was.

    Differentiable via custom VJP: the forward kernel emits the per-row
    log-sum-exp; the backward is one backward kernel, two where the dQ
    column does not fit — dK/dV's grid with dQ's float32 column of the
    step's heads resident in VMEM (`T × G·D × 4` bytes, twice: 8 MiB a
    buffer at T = 16,384 and a head of 128), five products a tile; past
    the fused call's cap on counted VMEM (T = 65,536 at a head of 128)
    the standard two-kernel Pallas split (dK/dV sweeping q blocks per kv
    block, dQ sweeping kv blocks per q block), seven — with blockwise
    probability recompute in VMEM either way: no HBM round trip for the
    probability matrices.
    """
    repeated = 2 * (k.shape[2] != q.shape[2])
    k, v = _repeat_kv(q, k, v)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window {window}: a causal mask's last keys, "
                             f"at least the position itself")
        if window >= q.shape[1]:
            window = None
    return _flash(q, k, v, causal, block_q, block_k, interpret, scale,
                  repeated, window)
