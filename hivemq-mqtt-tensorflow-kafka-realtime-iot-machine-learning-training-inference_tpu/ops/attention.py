"""Attention ops: reference implementation + Pallas flash-attention kernel.

The reference system's only sequence model is an LSTM at look_back=1
(SURVEY §5 'long-context: nothing') — but this framework treats long per-car
sensor histories as first-class: fleets emit unbounded streams, and anomaly
models that look at hours of context need sequence lengths the LSTM path
never contemplated.  The attention stack here:

- `attention_reference`: straight jnp softmax attention — the oracle for
  every other path, and the XLA-fused fallback on CPU.
- `flash_attention`: blocked online-softmax attention as a Pallas TPU
  kernel — O(T) memory instead of O(T²), MXU-shaped [128×128] tiles, the
  single-chip hot op of the transformer model family.
- `blockwise_update`: one online-softmax accumulation step, shared between
  the flash kernel's inner loop (conceptually) and the ring-attention
  cross-chip loop (`parallel.ring_attention`), which is the same math with
  the KV blocks arriving over ICI instead of from VMEM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

#: the kernels' names in a device trace and in the HLO: one per kernel,
#: whichever grid (dense or triangular) runs it
FWD_KERNEL = "iotml_flash_fwd"
BWD_DKV_KERNEL = "iotml_flash_bwd_dkv"
BWD_DQ_KERNEL = "iotml_flash_bwd_dq"


def _causal_tiles(nq: int, nk: int, block_q: int, block_k: int,
                  order: str) -> tuple:
    """Enumerate the LIVE causal tiles as (i_map, j_map) int32 arrays.

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
    no Python loop), shipped through scalar prefetch.  At the benched
    long-context shape (T=65,536, 1024² tiles) that is 2,080 tiles =
    16 KB; callers picking tiny blocks at huge T pay O((T/block)²)
    map memory, which `_flash_forward` caps (falls back to the dense
    grid past _TRI_TILE_CAP) so the prefetch stream can never outgrow
    SMEM-class storage."""
    if order == "row":
        jmax = np.minimum(nk - 1, (np.arange(nq, dtype=np.int64) * block_q
                                   + block_q - 1) // block_k)
        counts = jmax + 1
        im = np.repeat(np.arange(nq, dtype=np.int64), counts)
        # j runs 0..jmax within each row: global arange minus the row start
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        jm = np.arange(counts.sum(), dtype=np.int64) - starts
    else:
        imin = np.minimum(nq - 1, (np.arange(nk, dtype=np.int64) * block_k)
                          // block_q)
        counts = nq - imin
        jm = np.repeat(np.arange(nk, dtype=np.int64), counts)
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        im = np.arange(counts.sum(), dtype=np.int64) - starts + \
            np.repeat(imin, counts)
    return (im.astype(np.int32), jm.astype(np.int32))


#: triangular-grid cap: above this many live tiles the scalar-prefetch
#: maps (2 × 4 B × tiles, × 3 kernels) would outgrow SMEM-class storage —
#: fall back to the dense grid, which has O(1) grid metadata.  65,536
#: tiles = 512 KB of maps; every practical (T, block) pairing for this
#: framework sits far below it (65,536 tokens at 1024² → 2,080 tiles;
#: 128² blocks stay under the cap to T = 46k).
_TRI_TILE_CAP = 65_536


def _tri_tile_count(nq: int, nk: int, block_q: int, block_k: int) -> int:
    """Live-tile count of the causal triangle (row order; col is equal)."""
    jmax = np.minimum(nk - 1, (np.arange(nq, dtype=np.int64) * block_q
                               + block_q - 1) // block_k)
    return int((jmax + 1).sum())


def attention_reference(q, k, v, causal: bool = True,
                        q_offset: int = 0, k_offset: int = 0):
    """Plain softmax attention. q,k,v: [B, T, H, D] → [B, Tq, H, D].

    q_offset/k_offset give the global positions of local blocks so the
    causal mask stays correct under sequence sharding.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])[:, None]
        kpos = k_offset + jnp.arange(k.shape[1])[None, :]
        mask = qpos >= kpos
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


# --------------------------------------------------------------------- pallas
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                  scale: float, causal: bool, block_q: int, block_k: int):
    """Flash attention kernel.  Grid: (batch*heads, q_blocks, kv_blocks) —
    the kv dimension iterates sequentially on-core, so K/V stream through
    VMEM one [block_k, D] tile at a time (O(T) VMEM, long-context safe) and
    the online-softmax state lives in scratch that persists across the kv
    iterations of one q block."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def compute():
        # EVERY matmul rides the MXU at the INPUT dtype (bf16 inputs →
        # bf16 systolic passes at ~4× the f32 rate, f32 ACCUMULATION
        # always).  QK's bf16 products are exact (inputs are bf16); the
        # scale is applied to the f32 scores afterwards.  P is computed in
        # f32 (softmax stability) then cast to the input dtype for P·V —
        # the standard flash-attention trade: an f32 P·V matmul runs at ¼
        # the MXU rate and capped this kernel's whole-step MFU at ~33%
        # (see ARCHITECTURE.md roofline); the bf16 P rounding (~3 decimal
        # digits) is below the bf16 output's own quantization.
        s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + i * block_q
            kj = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + j * block_k
            s = jnp.where(qi >= kj, s, NEG_INF)
        m = m_s[:]
        l = l_s[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alive = m_new > NEG_INF / 2
        corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
        p = jnp.where(alive, jnp.exp(s - m_new), 0.0)
        m_s[:] = m_new
        l_s[:] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # whole KV block strictly in the future of this q block → skip
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            compute()
    else:
        compute()

    @pl.when(j == nk - 1)
    def _emit():
        l = l_s[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc[:] / safe_l).astype(o_ref.dtype)
        # log-sum-exp per query row (needed by the custom-VJP backward)
        lse_ref[:] = jnp.where(l == 0.0, NEG_INF, m_s[:] + jnp.log(safe_l))


def _flash_kernel_tri(im_ref, jm_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc, m_s, l_s, *, scale: float, block_q: int,
                      block_k: int, nk: int):
    """Causal flash forward on the TRIANGULAR grid: the grid's second
    axis walks only the live lower-triangle tiles (row-major), with the
    (i, j) tile coordinates arriving via scalar prefetch.  Strictly-future
    tiles no longer exist, so they pay neither their K/V DMA nor a grid
    step (the dense grid's `pl.when` skip still paid both — measured at
    ≈½ a computed tile, ARCHITECTURE.md roofline lever 2)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    i = im_ref[t]
    j = jm_ref[t]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # same math + dtype policy as _flash_kernel (see its comment): bf16
    # systolic passes, f32 accumulation, f32 softmax, P cast for P·V
    s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # the elementwise causal mask runs on EVERY tile even though only
    # diagonal-straddling tiles need it: branch-specializing it behind a
    # lax.cond was MEASURED SLOWER (54.2% vs 57.7% MFU same-session —
    # the cond defeats Mosaic's fusion/pipelining and, in the backward,
    # the duplicated branch temporaries blow the 16 MB scoped-VMEM
    # budget at 1024^2 tiles).  Roofline lever 3 stays on the table via
    # cheaper masks, not control flow.
    qi = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + i * block_q
    kj = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1) + j * block_k
    s = jnp.where(qi >= kj, s, NEG_INF)
    m = m_s[:]
    l = l_s[:]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alive = m_new > NEG_INF / 2
    corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
    p = jnp.where(alive, jnp.exp(s - m_new), 0.0)
    m_s[:] = m_new
    l_s[:] = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc[:] = acc[:] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # last live tile of this q row = the diagonal block
    jmax = jnp.minimum(nk - 1, (i * block_q + block_q - 1) // block_k)

    @pl.when(j == jmax)
    def _emit():
        lf = l_s[:]
        safe_l = jnp.where(lf == 0.0, 1.0, lf)
        o_ref[:] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[:] = jnp.where(lf == 0.0, NEG_INF,
                               m_s[:] + jnp.log(safe_l))


def _flash_forward(q, k, v, causal: bool, block_q: int,
                   block_k: int, interpret: bool):
    """Run the Pallas kernel; returns (out [B,T,H,D], lse [B,H,T])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    Tq = ((T + block_q - 1) // block_q) * block_q
    Tk = ((T + block_k - 1) // block_k) * block_k
    if not causal and Tk != T:
        # padded keys are only excluded by the causal mask; non-causal
        # callers must supply block-multiple sequence lengths
        raise ValueError(f"non-causal flash attention needs T % {block_k} == 0")
    if Tq != T:
        pad = [(0, 0), (0, Tq - T), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
    if Tk != T:
        pad = [(0, 0), (0, Tk - T), (0, 0), (0, 0)]
        # pad keys so padded positions never win the max: values 0, and the
        # causal mask (global positions) excludes them for every real query
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # layout: fold batch & heads into the grid's first axis, T-major blocks
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)

    out_shape = [
        jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        jax.ShapeDtypeStruct((B * H, Tq, 1), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, D), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
    ]
    tri = causal and _tri_tile_count(Tq // block_q, Tk // block_k,
                                     block_q, block_k) <= _TRI_TILE_CAP
    if tri:
        # triangular grid: only live tiles exist (see _flash_kernel_tri)
        im, jm = _causal_tiles(Tq // block_q, Tk // block_k,
                               block_q, block_k, "row")
        kernel = functools.partial(_flash_kernel_tri, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   nk=Tk // block_k)
        out, lse = pl.pallas_call(
            kernel,
            name=FWD_KERNEL,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B * H, len(im)),
                in_specs=[
                    pl.BlockSpec((None, block_q, D),
                                 lambda b, t, im, jm: (b, im[t], 0)),
                    pl.BlockSpec((None, block_k, D),
                                 lambda b, t, im, jm: (b, jm[t], 0)),
                    pl.BlockSpec((None, block_k, D),
                                 lambda b, t, im, jm: (b, jm[t], 0)),
                ],
                out_specs=[
                    pl.BlockSpec((None, block_q, D),
                                 lambda b, t, im, jm: (b, im[t], 0)),
                    pl.BlockSpec((None, block_q, 1),
                                 lambda b, t, im, jm: (b, im[t], 0)),
                ],
                scratch_shapes=scratch_shapes,
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(im), jnp.asarray(jm), qf, kf, vf)
    else:
        kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                                   block_q=block_q, block_k=block_k)
        out, lse = pl.pallas_call(
            kernel,
            name=FWD_KERNEL,
            grid=(B * H, Tq // block_q, Tk // block_k),
            in_specs=[
                pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(qf, kf, vf)
    out = out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)[:, :T]
    lse = lse.reshape(B, H, Tq)[:, :, :T]
    return out, lse


def _bwd_common(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
                scale, causal, block_q, block_k, t_real, i, j):
    """Shared recompute for both backward kernels: returns (p, ds) f32.

    Matmul dtype policy mirrors the forward: score/dP matmuls run at the
    input dtype (exact products for bf16, MXU bf16 rate, f32 accumulate);
    p/ds stay f32 — they are exp-of-f32 quantities the gradient
    tolerances pin.  The mask runs on every tile: branch-specializing it
    (lax.cond on straddle/tail tiles) was measured slower AND blew the
    scoped-VMEM budget at 1024^2 tiles — see the forward kernel's note."""
    s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qi = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + i * block_q
    kj = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1) + j * block_k
    mask = kj < t_real
    if causal:
        mask = mask & (qi >= kj)
    p = jnp.where(mask, jnp.exp(s - lse_ref[:]), 0.0)
    dp = jax.lax.dot_general(do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[:]) * scale
    return p, ds


def _flash_bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                          causal, block_q, block_k, t_real):
    """dK/dV: grid (BH, kv_blocks, q_blocks) — for one kv block, stream
    the q blocks through VMEM accumulating dk/dv in scratch; p never
    touches HBM (the jnp fallback's bandwidth wall)."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        p, ds = _bwd_common(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            scale=scale, causal=causal, block_q=block_q,
                            block_k=block_k, t_real=t_real, i=i, j=j)
        # p/ds cast to the input dtype: bf16 MXU passes with f32
        # accumulation (see the forward's dtype-policy note + the
        # ARCHITECTURE.md roofline — f32 operand matmuls were the MFU cap)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # q blocks strictly before this kv block contribute nothing
        @pl.when(i * block_q + (block_q - 1) >= j * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(i == nq - 1)
    def _emit():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                         dq_ref, dq_acc, *, scale, causal, block_q,
                         block_k, t_real):
    """dQ: grid (BH, q_blocks, kv_blocks) — one q block accumulates over
    its (causally relevant) kv blocks."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        _, ds = _bwd_common(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            scale=scale, causal=causal, block_q=block_q,
                            block_k=block_k, t_real=t_real, i=i, j=j)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            compute()
    else:
        compute()

    @pl.when(j == nk - 1)
    def _emit():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel_tri(im_ref, jm_ref, q_ref, do_ref, lse_ref,
                              delta_ref, k_ref, v_ref, dk_ref, dv_ref,
                              dk_acc, dv_acc, *, scale, block_q, block_k,
                              t_real, nq):
    """dK/dV on the triangular grid: column-major live tiles (the scratch
    accumulates q blocks within one kv column).  A column entirely in the
    future of every query keeps one dead diagonal tile whose mask zeroes
    p/ds, so its dk/dv block is still zero-written (see _causal_tiles)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    i = im_ref[t]
    j = jm_ref[t]
    imin = jnp.minimum(nq - 1, (j * block_k) // block_q)

    @pl.when(i == imin)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    p, ds = _bwd_common(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        scale=scale, causal=True, block_q=block_q,
                        block_k=block_k, t_real=t_real, i=i, j=j)
    dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
        p.astype(do_ref.dtype), do_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
        ds.astype(q_ref.dtype), q_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _emit():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel_tri(im_ref, jm_ref, q_ref, do_ref, lse_ref,
                             delta_ref, k_ref, v_ref, dq_ref, dq_acc, *,
                             scale, block_q, block_k, t_real, nk):
    """dQ on the triangular grid: row-major live tiles (one q block
    accumulates its causally-relevant kv blocks)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    i = im_ref[t]
    j = jm_ref[t]

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    _, ds = _bwd_common(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        scale=scale, causal=True, block_q=block_q,
                        block_k=block_k, t_real=t_real, i=i, j=j)
    dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    jmax = jnp.minimum(nk - 1, (i * block_q + block_q - 1) // block_k)

    @pl.when(j == jmax)
    def _emit():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


# backward tile cap: 1024² measured fastest on v5e (the three [bq, bk]
# f32 temporaries fit VMEM; 2048² fails to compile) — sweep in PARITY
_BWD_CAP = 1024


def _flash_backward(q, k, v, out, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool):
    """Pallas flash-attention backward: the standard two-kernel split
    (dkv sweeping q per kv block; dq sweeping kv per q block — p/ds
    recomputed blockwise in VMEM, never materialized to HBM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    # independent backward tile sizes (see _BWD_CAP)
    bq = min(block_q, _BWD_CAP)
    bk = min(block_k, _BWD_CAP)
    Tq = ((T + bq - 1) // bq) * bq
    Tk = ((T + bk - 1) // bk) * bk

    do_f = do.astype(jnp.float32)
    # rowwise D_i = sum_d dO_i·O_i (softmax-jacobian diagonal term)
    delta = jnp.einsum("bqhd,bqhd->bhq", do_f, out.astype(jnp.float32))

    def fold_q(x, pad_value=0.0):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, T, D)
        return jnp.pad(x, [(0, 0), (0, Tq - T), (0, 0)],
                       constant_values=pad_value)

    qf = fold_q(q)
    dof = fold_q(do)
    kf = jnp.pad(k.transpose(0, 2, 1, 3).reshape(B * H, T, D),
                 [(0, 0), (0, Tk - T), (0, 0)])
    vf = jnp.pad(v.transpose(0, 2, 1, 3).reshape(B * H, T, D),
                 [(0, 0), (0, Tk - T), (0, 0)])
    # padded q rows: +BIG lse → p = exp(s - BIG) = 0, so they contribute
    # nothing to dk/dv and their dq rows are sliced off
    lse_f = jnp.pad(lse.reshape(B * H, T, 1),
                    [(0, 0), (0, Tq - T), (0, 0)],
                    constant_values=1e30)
    delta_f = jnp.pad(delta.reshape(B * H, T, 1),
                      [(0, 0), (0, Tq - T), (0, 0)])

    q_spec_i = pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0))
    q_spec_j = pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0))
    r_spec_i = pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0))
    r_spec_j = pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0))
    kv_spec_i = pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0))
    kv_spec_j = pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0))

    dkv_out_shape = [jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
                     jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype)]
    dkv_scratch = [pltpu.VMEM((bk, D), jnp.float32),
                   pltpu.VMEM((bk, D), jnp.float32)]
    tri = causal and _tri_tile_count(Tq // bq, Tk // bk,
                                     bq, bk) <= _TRI_TILE_CAP
    if tri:
        imc, jmc = _causal_tiles(Tq // bq, Tk // bk, bq, bk, "col")
        dkv_kernel = functools.partial(
            _flash_bwd_dkv_kernel_tri, scale=scale, block_q=bq,
            block_k=bk, t_real=T, nq=Tq // bq)
        q_tri = pl.BlockSpec((None, bq, D),
                             lambda b, t, im, jm: (b, im[t], 0))
        r_tri = pl.BlockSpec((None, bq, 1),
                             lambda b, t, im, jm: (b, im[t], 0))
        kv_tri = pl.BlockSpec((None, bk, D),
                              lambda b, t, im, jm: (b, jm[t], 0))
        dk_f, dv_f = pl.pallas_call(
            dkv_kernel,
            name=BWD_DKV_KERNEL,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B * H, len(imc)),
                in_specs=[q_tri, q_tri, r_tri, r_tri, kv_tri, kv_tri],
                out_specs=[kv_tri, kv_tri],
                scratch_shapes=dkv_scratch,
            ),
            out_shape=dkv_out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(imc), jnp.asarray(jmc), qf, dof, lse_f, delta_f,
          kf, vf)
    else:
        dkv_kernel = functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, t_real=T)
        dk_f, dv_f = pl.pallas_call(
            dkv_kernel,
            name=BWD_DKV_KERNEL,
            grid=(B * H, Tk // bk, Tq // bq),
            in_specs=[q_spec_j, q_spec_j, r_spec_j, r_spec_j,
                      kv_spec_j, kv_spec_j],
            out_specs=[pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
                       pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0))],
            out_shape=dkv_out_shape,
            scratch_shapes=dkv_scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(qf, dof, lse_f, delta_f, kf, vf)

    if tri:
        imr, jmr = _causal_tiles(Tq // bq, Tk // bk, bq, bk, "row")
        dq_kernel = functools.partial(
            _flash_bwd_dq_kernel_tri, scale=scale, block_q=bq,
            block_k=bk, t_real=T, nk=Tk // bk)
        dq_f = pl.pallas_call(
            dq_kernel,
            name=BWD_DQ_KERNEL,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B * H, len(imr)),
                in_specs=[q_tri, q_tri, r_tri, r_tri, kv_tri, kv_tri],
                out_specs=q_tri,
                scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(imr), jnp.asarray(jmr), qf, dof, lse_f, delta_f,
          kf, vf)
    else:
        dq_kernel = functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, t_real=T)
        dq_f = pl.pallas_call(
            dq_kernel,
            name=BWD_DQ_KERNEL,
            grid=(B * H, Tq // bq, Tk // bk),
            in_specs=[q_spec_i, q_spec_i, r_spec_i, r_spec_i,
                      kv_spec_i, kv_spec_i],
            out_specs=pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(qf, dof, lse_f, delta_f, kf, vf)

    def unfold(x, Tp):
        return x.reshape(B, H, Tp, D).transpose(0, 2, 1, 3)[:, :T]

    return unfold(dq_f, Tq), unfold(dk_f, Tk), unfold(dv_f, Tk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Pallas flash attention. q,k,v: [B, T, H, D] → [B, T, H, D].

    T is padded to the block size internally (padding keys are masked out by
    the causal structure; non-causal callers must pass T multiple of the
    block).  `interpret=True` runs the same kernel on CPU for tests.

    Differentiable via custom VJP: the forward kernel emits the per-row
    log-sum-exp; the backward is the standard two-kernel Pallas split
    (dK/dV sweeping q blocks per kv block, dQ sweeping kv blocks per q
    block) with blockwise probability recompute in VMEM — O(T·block)
    memory and no HBM round trip for the probability matrices.
    """
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, do, causal, block_q, block_k,
                           interpret)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
