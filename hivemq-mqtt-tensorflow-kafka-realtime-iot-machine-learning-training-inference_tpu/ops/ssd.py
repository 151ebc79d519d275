"""State-space (Mamba-2) sequence ops: the chunked selective scan and the
causal depthwise convolution ahead of it.

The recurrence, per head (x_t a P-vector, B_t and C_t N-vectors shared
by all heads, Δ_t > 0 and a < 0 scalars, S an P×N state, S_0 = 0):

    S_t = exp(Δ_t a) S_{t-1} + Δ_t x_t ⊗ B_t        y_t = S_t C_t

Step by step that is T dependent updates of a state the MXU never
sees.  `ssd_scan` computes the same y in the chunked ("state-space
dual") form: within a chunk of Q positions the outputs are one masked,
decay-weighted attention-like product `((C Bᵀ) ⊙ L) (Δ x)`; each chunk
leaves a state behind; the states are carried across chunks by one
small product over the chunk axis; and the carried state enters a
chunk's outputs through C.  Everything is an einsum XLA maps to the MXU,
and `jax.grad` of it is the chunked backward: the same products
transposed, nothing sequential.

Every decay is the exponential of a DIFFERENCE of cumulative sums of
Δ·a, taken after the causal mask: the differences kept are ≤ 0, so no
`exp` of a positive number is ever formed, however long the chunk or
large Δ·|a| (the separate factors exp(cum_t)·exp(-cum_s) overflow
float32 once Δ·|a| sums past 88).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


def causal_conv1d(x, kernel, bias):
    """Depthwise causal convolution over time, the plain form the tests
    hold `causal_conv1d_fused` to: x [B, T, C], kernel [K, C], bias [C]
    → [B, T, C], with y_t = bias + Σ_k kernel[k] · x_{t-(K-1)+k} and
    x_t = 0 for t < 0 (left-padded with zeros).

    K shifted multiply-adds: a depthwise convolution has no contraction
    for the MXU (2K operations an element against 8 bytes moved).  XLA
    does NOT fuse the taps into one pass over x: a forward and a
    backward of `silu` of this at [1, 4096, 4352] compile, for a v5e,
    to 16 passes over a tensor (a padded copy of x a tap, four
    [B, T, C] temporaries in the backward read back at four row
    offsets) where 5 are needed — which is why the model calls the
    Pallas kernels below and not this.
    """
    K, T = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = bias + xp[:, :T] * kernel[0]
    for k in range(1, K):
        y = y + xp[:, k:k + T] * kernel[k]
    return y


# ------------------------------------------------- the convolution, in Pallas
# The kernels work on the TRANSPOSE, [B, channels, T]: time on the lanes,
# channels on the sublanes.  That is the layout XLA gives the mixer's
# tensors between `in_proj` and the scan's products in the compiled fit
# (T-minor: in_proj's own output, the scan's operands, the cotangents
# that come back), so a `swapaxes` around the calls is a change of name
# and not a copy; kernels on [B, T, channels] rows forced every
# neighbour through a transposing copy and the fit ran 5% SLOWER than
# with no kernel at all (PERF.md §6, PR 29).

#: positions of a lane tile: the halo a block reads before itself (and
#: the backward carries after itself) is one such tile, so K - 1 <= 128
_LANES = 128
#: bytes of one operand's block: the backward pipelines three operands
#: (x, dy, dx) twice each, 12 of the 16 MiB of scoped VMEM Mosaic grants
#: on a v5e without a call here raising it
_CONV_BLOCK_BYTES = 2 ** 21
#: the most positions a block holds
_CONV_MAX_T = 4096
#: what the kernels may apply to the convolution's sum before they store
#: it: `silu` (Mamba-2's) or nothing (`none`: a gated short convolution's,
#: whose gates are its caller's)
CONV_ACTIVATIONS = ("silu", "none")
#: what an iteration of the kernels' loops takes: so many channels
#: (whole sublane tiles) by so many lane tiles.  The chain from a load
#: through the rolls, the exponential and the reciprocal to the store is
#: a hundred cycles long and iterations do not overlap, so an iteration
#: wants many independent tiles: a whole block's row where it can
_CONV_ROWS = 32
_CONV_TILES = 32


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class ConvGeometry(NamedTuple):
    """How one run of channels is cut for the convolution kernels."""
    block_c: int     # channels a block holds: whole sublane tiles
    block_t: int     # positions a block holds: whole lane tiles
    c_pad: int       # the run's channels in whole blocks
    t_pad: int       # T in whole blocks
    in_place: bool   # read as row blocks of the caller's own array:
    #                  nothing was padded, nothing sliced out


def conv_geometry(T: int, width: int, column: int, itemsize: int
                  ) -> ConvGeometry:
    """Blocks for `width` channels that start at channel `column` of
    the array they lie in, from the shape alone — the one place that
    knows them.

    Time is lanes: T in whole 128-lane tiles, in one block up to
    `_CONV_MAX_T`, else its largest divisor in whole tiles under that.
    Channels are sublanes: the most whole sublane tiles that divide
    both the width and the column — so that the run is whole row blocks
    of the transposed array as it stands — and keep a block within
    `_CONV_BLOCK_BYTES`.  Where T fills no lane tile or the sublanes
    divide neither, the wrapper slices the run out and pads it to
    whole blocks (a copy, counted)."""
    tile = 32 // itemsize
    t_pad = _round_up(T, _LANES)
    block_t = next(t for t in range(min(t_pad, _CONV_MAX_T), 0, -_LANES)
                   if t_pad % t == 0)
    most = max(tile, _CONV_BLOCK_BYTES // (block_t * itemsize) // tile * tile)
    rows = math.gcd(width, column)
    if rows % tile:
        block_c = min(most, _round_up(width, tile))
        return ConvGeometry(block_c, block_t, _round_up(width, block_c),
                            t_pad, False)
    block_c = next(c for c in range(min(rows, most), 0, -tile)
                   if rows % c == 0)
    return ConvGeometry(block_c, block_t, width, t_pad, t_pad == T)


def _moved(rolled, rolled_beside, shift: int, lane):
    """A lane tile moved `shift` lanes to the right (left where
    negative): the tile rolled by that, the lanes the move empties
    filled from the neighbouring tile rolled by the same."""
    fill = lane < shift if shift > 0 else lane >= _LANES + shift
    return jnp.where(fill, rolled_beside, rolled)


def _loop_shape(block_c: int, block_t: int, itemsize: int):
    """(channels, lane tiles) an iteration of a kernel's loops takes:
    the most whole sublane tiles within `_CONV_ROWS` and the most lane
    tiles within `_CONV_TILES` that divide the block."""
    tile, tiles = 32 // itemsize, block_t // _LANES
    return (next(r for r in range(max(tile, _CONV_ROWS // tile * tile), 0,
                                  -tile) if block_c % r == 0),
            next(n for n in range(min(_CONV_TILES, tiles), 0, -1)
                 if tiles % n == 0))


def _row_constants(k_ref, b_ref, r, n: int):
    """The taps' weights and the bias of the channels `r`, each spread
    over a lane tile once, ahead of the loop over the tiles."""
    f32 = jnp.float32
    return ([jnp.broadcast_to(k_ref[r, k:k + 1].astype(f32), (n, _LANES))
             for k in range(k_ref.shape[1])],
            jnp.broadcast_to(b_ref[r, :].astype(f32), (n, _LANES)))


def _conv_fwd_step(*refs, K: int, halo: bool, activation: str):
    """One [block_c, block_t] block of act(bias + Σ_k kernel[k] ·
    x_{t-(K-1)+k}), act SiLU or nothing: a sublane tile of channels at
    a time, lane tile by lane tile, a tile's taps, sum and activation
    in registers.  The K-1 positions before a lane tile are the end of
    the tile before it — kept, rolled, from tile to tile; for a block's
    first tile `h_ref` (the tile before the block, through its own
    index map; zeros before the first block, so no padded x exists)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x_ref, h_ref, k_ref, b_ref, o_ref = refs if halo else \
        (refs[0], None) + refs[1:]
    f32 = jnp.float32
    bc, bt = x_ref.shape[1:]
    n, per = _loop_shape(bc, bt, x_ref.dtype.itemsize)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)
    shifts = range(1, K)                  # tap k reads K-1-k lanes back
    inner = pl.program_id(2) > 0          # not the first time block

    def rows(g, _):
        r = pl.ds(pl.multiple_of(g * n, n), n)
        w, bias = _row_constants(k_ref, b_ref, r, n)
        before = jnp.zeros((n, _LANES), f32)
        if halo:
            before = jnp.where(inner, h_ref[0, r, :].astype(f32), 0.0)

        def tiles(j, beside):
            for u in range(per):
                at = pl.ds(pl.multiple_of((j * per + u) * _LANES, _LANES),
                           _LANES)
                cur = x_ref[0, r, at].astype(f32)
                mine = [pltpu.roll(cur, s, 1) for s in shifts]
                pre = bias + cur * w[K - 1]
                for s, rolled, was in zip(shifts, mine, beside):
                    pre = pre + _moved(rolled, was, s, lane) * w[K - 1 - s]
                if activation == "silu":
                    pre = pre * jax.nn.sigmoid(pre)
                o_ref[0, r, at] = pre.astype(o_ref.dtype)
                beside = mine
            return beside

        jax.lax.fori_loop(0, bt // (per * _LANES), tiles,
                          [pltpu.roll(before, s, 1) for s in shifts])
        return 0

    jax.lax.fori_loop(0, bc // n, rows, 0)


def _conv_bwd_step(*refs, K: int, halo: bool, activation: str):
    """One block of the backward, time blocks and a block's lane tiles
    taken LAST first: the pre-activation again from x (nothing else was
    kept), g = dy · silu′(pre) — g = dy where nothing was applied, and
    no pre-activation is formed — dx_t = Σ_k kernel[k] · g_{t+(K-1)-k} —
    the K-1 positions of g after a tile are the start of the tile
    handled just before it, kept rolled from tile to tile and in
    `g_after` from block to block — and the tap and bias gradients
    summed lane by lane in registers, then over the lanes into output
    blocks that stay resident over the time axis."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x_ref, h_ref, dy_ref, k_ref, b_ref, dx_ref, dk_ref, db_ref, g_after = \
        refs if halo else (refs[0], None) + refs[1:]
    f32 = jnp.float32
    bc, bt = x_ref.shape[1:]
    n, per = _loop_shape(bc, bt, x_ref.dtype.itemsize)
    groups = bt // (per * _LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)
    shifts = range(1, K)
    step = pl.program_id(2)            # 0 is the LAST time block
    inner = step < pl.num_programs(2) - 1

    @pl.when(step == 0)
    def _first():
        g_after[...] = jnp.zeros_like(g_after)
        dk_ref[...] = jnp.zeros_like(dk_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    def rows(g_, _):
        r = pl.ds(pl.multiple_of(g_ * n, n), n)
        w, bias = _row_constants(k_ref, b_ref, r, n)
        ahead = jnp.zeros((n, _LANES), f32)   # what lies before the block
        if halo:
            ahead = jnp.where(inner, h_ref[0, r, :].astype(f32), 0.0)

        def tiles(i, carry):
            """A group of lane tiles, the last first; carry: g of the
            tile after, rolled; the sums."""
            after, dk, db = carry
            t0 = pl.multiple_of((groups - 1 - i) * per * _LANES, _LANES)
            at = [pl.ds(t0 + u * _LANES, _LANES) for u in range(per)]
            before = jnp.where(
                i == groups - 1, ahead,
                x_ref[0, r, pl.ds(pl.multiple_of(
                    jnp.maximum(t0 - _LANES, 0), _LANES), _LANES)].astype(f32))
            cur = [before] + [x_ref[0, r, a].astype(f32) for a in at]
            rolled = [[pltpu.roll(v, s, 1) for s in shifts] for v in cur]
            for u in reversed(range(per)):
                # taps[i]: x moved i lanes back, tap K-1-i's operand
                taps = [cur[u + 1]] + [
                    _moved(rolled[u + 1][s - 1], rolled[u][s - 1], s, lane)
                    for s in shifts]
                if activation == "silu":
                    pre = bias
                    for back, tap in enumerate(taps):
                        pre = pre + tap * w[K - 1 - back]
                    sg = jax.nn.sigmoid(pre)
                    g = dy_ref[0, r, at[u]].astype(f32) * (
                        sg * (1.0 + pre * (1.0 - sg)))
                else:
                    g = dy_ref[0, r, at[u]].astype(f32)
                mine = [pltpu.roll(g, _LANES - s, 1) for s in shifts]
                dx = g * w[K - 1]
                for s, m, was in zip(shifts, mine, after):
                    dx = dx + _moved(m, was, -s, lane) * w[K - 1 - s]
                dx_ref[0, r, at[u]] = dx.astype(dx_ref.dtype)
                after = mine
                dk = [a + g * tap for a, tap in zip(dk, taps)]
                db = db + g
            return after, dk, db

        zeros = jnp.zeros((n, _LANES), f32)
        far = g_after[r, :]
        after, dk, db = jax.lax.fori_loop(
            0, groups, tiles,
            ([pltpu.roll(far, _LANES - s, 1) for s in shifts],
             [zeros] * K, zeros))
        # the block's first tile of g, un-rolled again, for the block before
        g_after[r, :] = pltpu.roll(after[0], 1, 1) if K > 1 else zeros
        for back in range(K):
            dk_ref[0, r, K - 1 - back:K - back] += jnp.sum(
                dk[back], axis=1, keepdims=True)
        db_ref[0, r, :] += jnp.sum(db, axis=1, keepdims=True)
        return 0

    jax.lax.fori_loop(0, bc // n, rows, 0)


def _conv_cost(elements: int, K: int, itemsize: int, passes: int,
               activation: str):
    """What a call moves and computes, for XLA's scheduler: `passes`
    tensors of `elements` through HBM (x and y; x, dy and dx), 2K
    operations an element for the taps — again for dx and for the tap
    gradients in the backward, and a third time there for the
    pre-activation where an activation was applied — and the
    activation's own eight with one exponential."""
    from jax.experimental import pallas as pl

    silu = activation == "silu"
    rounds = 1 if passes == 2 else 2 + silu
    return pl.CostEstimate(flops=(2 * K * rounds + 8 * silu) * elements,
                           transcendentals=elements * silu,
                           bytes_accessed=passes * elements * itemsize)


def _conv_operand(xt, column: int, width: int, geom: ConvGeometry):
    """The run of channels as the kernels read it: the caller's array
    and the run's first row block, or — where `conv_geometry` could not
    place it — a copy sliced out and padded to whole blocks."""
    if geom.in_place:
        return xt, column // geom.block_c
    run = jax.lax.slice_in_dim(xt, column, column + width, axis=1)
    return _whole(run, geom), 0


def _whole(run, geom: ConvGeometry):
    """[B, width, T], or the taps' [width, K], padded to whole blocks."""
    rows = (0, geom.c_pad - run.shape[-2])
    if run.ndim == 2:
        return jnp.pad(run, (rows, (0, 0)))
    return jnp.pad(run, ((0, 0), rows, (0, geom.t_pad - run.shape[2])))


def _conv_specs(K: int, geom: ConvGeometry, first: int, at):
    """Block specs of a call on grid (B, row blocks, time blocks), the
    time block of grid step t being `at(t)`: the run's blocks in the
    array they lie in, the lane tile ahead of each, an array of the
    run's own, the taps and the bias."""
    from jax.experimental import pallas as pl

    bc, bt = geom[:2]
    up = bt // _LANES
    return (
        pl.BlockSpec((1, bc, bt), lambda b, c, t: (b, first + c, at(t))),
        pl.BlockSpec((1, bc, _LANES), lambda b, c, t: (
            b, first + c, jnp.maximum(at(t) * up - 1, 0))),
        pl.BlockSpec((1, bc, bt), lambda b, c, t: (b, c, at(t))),
        pl.BlockSpec((bc, K), lambda b, c, t: (c, 0)),
        pl.BlockSpec((bc, 1), lambda b, c, t: (c, 0)))


@functools.partial(jax.jit, static_argnames=("column", "geom", "interpret",
                                             "activation"))
def _conv_forward(xt, kernel, bias, column: int, geom: ConvGeometry,
                  interpret: bool, activation: str):
    """act(conv) of the `kernel.shape[1]` channels of xt [B, channels,
    T] from `column` on: [B, width, T].  Jitted, as the flash calls
    are: a stack of equal layers traces and lowers it once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, _, T), (K, width) = xt.shape, kernel.shape
    bc, bt, c_pad, t_pad, _ = geom
    xt, first = _conv_operand(xt, column, width, geom)
    halo = t_pad > bt
    block, ahead, own, taps, col = _conv_specs(K, geom, first, lambda t: t)
    out = pl.pallas_call(
        functools.partial(_conv_fwd_step, K=K, halo=halo,
                          activation=activation),
        grid=(B, c_pad // bc, t_pad // bt),
        in_specs=[block] + [ahead] * halo + [taps, col],
        out_specs=own,
        out_shape=jax.ShapeDtypeStruct((B, c_pad, t_pad), xt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=_conv_cost(B * t_pad * c_pad, K, xt.dtype.itemsize, 2,
                                 activation),
        interpret=interpret, name="iotml_conv_fwd",
    )(*[xt] * (1 + halo), _whole(kernel.T, geom),
      _whole(bias[:, None], geom))
    return out[:, :width, :T]


@functools.partial(jax.jit, static_argnames=("column", "geom", "interpret",
                                             "activation"))
def _conv_backward(xt, dyt, kernel, bias, column: int, geom: ConvGeometry,
                   interpret: bool, activation: str):
    """(dx [B, width, T], dkernel [K, width], dbias [width]) of
    `_conv_forward` at the cotangent dyt [B, width, T]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, _, T), (K, width) = xt.shape, kernel.shape
    bc, bt, c_pad, t_pad, _ = geom
    xt, first = _conv_operand(xt, column, width, geom)
    dyt = _whole(dyt, geom)
    nt = t_pad // bt
    halo = nt > 1
    block, ahead, own, taps, col = _conv_specs(
        K, geom, first, lambda t: nt - 1 - t)    # the last block first
    dx, dk, db = pl.pallas_call(
        functools.partial(_conv_bwd_step, K=K, halo=halo,
                          activation=activation),
        grid=(B, c_pad // bc, nt),
        in_specs=[block] + [ahead] * halo + [own, taps, col],
        out_specs=[own,
                   pl.BlockSpec((1, bc, K), lambda b, c, t: (b, c, 0)),
                   pl.BlockSpec((1, bc, 1), lambda b, c, t: (b, c, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, c_pad, t_pad), xt.dtype),
                   jax.ShapeDtypeStruct((B, c_pad, K), jnp.float32),
                   jax.ShapeDtypeStruct((B, c_pad, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bc, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=_conv_cost(B * t_pad * c_pad, K, xt.dtype.itemsize, 3,
                                 activation),
        interpret=interpret, name="iotml_conv_bwd",
    )(*[xt] * (1 + halo), dyt, _whole(kernel.T, geom),
      _whole(bias[:, None], geom))
    return (dx[:, :width, :T], dk.sum(0)[:width].T.astype(kernel.dtype),
            db.sum((0, 2))[:width].astype(bias.dtype))


def _runs(x, splits):
    """(first channel, width, geometry) of each run the outputs are
    split in."""
    starts = [sum(splits[:i]) for i in range(len(splits))]
    return [(c, w, conv_geometry(x.shape[1], w, c, x.dtype.itemsize))
            for c, w in zip(starts, splits)]


def _record_conv(kernel: str, B: int, runs, K: int, activation: str) -> None:
    """Say what engaged, as `_record` below does: the steps of the runs'
    calls together, the blocks of the widest run, the taps, whether the
    kernels applied an activation, and how many operands the wrapper
    copied ahead of the kernels — where a run is not read in place x,
    sliced out and padded, and in the backward dy, padded with it.
    Copies XLA makes of its own around a call, to turn an operand's
    layout, are not the wrapper's and are not counted:
    `tests/test_mosaic_aot.py` reads the layouts off a compiled fit."""
    from ..obs import metrics as obs_metrics

    geoms = [g for _, _, g in runs]
    obs_metrics.conv_grid_steps.set(
        sum(B * (g.c_pad // g.block_c) * (g.t_pad // g.block_t)
            for g in geoms), kernel=kernel)
    _, _, widest = max(runs, key=lambda r: r[1])
    obs_metrics.conv_block_t.set(widest.block_t)
    obs_metrics.conv_block_c.set(widest.block_c)
    obs_metrics.conv_taps.set(K)
    obs_metrics.conv_activation_fused.set(int(activation != "none"))
    obs_metrics.conv_operand_copies.set(
        sum((not g.in_place) * (1 + (kernel == "bwd")) for g in geoms),
        kernel=kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_fused(x, kernel, bias, splits, activation):
    return _conv_fused_fwd(x, kernel, bias, splits, activation)[0]


def _conv_fused_fwd(x, kernel, bias, splits, activation):
    from .fused_train import interpret_mode

    runs = _runs(x, splits)
    _record_conv("fwd", x.shape[0], runs, kernel.shape[0], activation)
    xt = jnp.swapaxes(x, 1, 2)
    out = tuple(jnp.swapaxes(_conv_forward(
        xt, kernel[:, c:c + w], bias[c:c + w], column=c, geom=g,
        interpret=interpret_mode(), activation=activation), 1, 2)
        for c, w, g in runs)
    return out, (x, kernel, bias)


def _conv_fused_bwd(splits, activation, kept, dys):
    from .fused_train import interpret_mode

    x, kernel, bias = kept
    runs = _runs(x, splits)
    _record_conv("bwd", x.shape[0], runs, kernel.shape[0], activation)
    xt = jnp.swapaxes(x, 1, 2)
    dxs, dks, dbs = zip(*(
        _conv_backward(xt, jnp.swapaxes(dy, 1, 2), kernel[:, c:c + w],
                       bias[c:c + w], column=c, geom=g,
                       interpret=interpret_mode(), activation=activation)
        for (c, w, g), dy in zip(runs, dys)))
    return (jnp.swapaxes(jnp.concatenate(dxs, axis=1), 1, 2),
            jnp.concatenate(dks, axis=1), jnp.concatenate(dbs))


_conv_fused.defvjp(_conv_fused_fwd, _conv_fused_bwd)


def causal_conv1d_fused(x, kernel, bias=None, *, splits=None,
                        activation: str = "silu"):
    """act(causal_conv1d(x, kernel, bias)) for x [B, T, C], kernel
    [K, C] and bias [C] (None: no bias, zeros to the kernels), split
    along the channels into runs of `splits` widths (one run of C where
    None): a tuple of [B, T, width].  `activation` is static, one of
    `CONV_ACTIVATIONS`: `silu`, or `none` for the sum as it stands.

    One Pallas kernel a direction (`iotml_conv_fwd`, `iotml_conv_bwd`,
    once a run) on the transposed arrays, time on the lanes: a forward
    reads x once and writes y once; a backward reads x and dy once and
    writes dx once, the tap and bias gradients reduced on the way; only
    x is kept for it.  A run is read where it lies in x and written as
    an array of its own, wherever `conv_geometry` can place it on the
    sublanes; says what engaged (`iotml_conv_*`).  (Runs, and not one
    call over all of C whose output the caller splits: with that whole
    output alive XLA keeps fewer of the MLP's operands in VMEM and the
    cell's step is 12% longer — PERF.md §6.)"""
    K, C = kernel.shape
    splits = (C,) if splits is None else tuple(splits)
    if sum(splits) != C or x.shape[2] != C:
        raise ValueError(f"runs {splits} are not the {C} taps' channels of "
                         f"{x.shape[2]} columns")
    if K - 1 > _LANES:
        raise ValueError(f"{K} taps reach past the {_LANES} positions "
                         "ahead of a block")
    if activation not in CONV_ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is none of "
                         f"{CONV_ACTIVATIONS}")
    if bias is None:
        bias = jnp.zeros((C,), kernel.dtype)
    return _conv_fused(x, kernel, bias, splits, activation)


def causal_conv1d_silu(x, kernel, bias, *, splits=None):
    """`causal_conv1d_fused` with the SiLU: Mamba-2's convolution."""
    return causal_conv1d_fused(x, kernel, bias, splits=splits)


def ssd_scan(x, dt, a, b, c, chunk: int):
    """The selective state-space recurrence above, chunked.

    x [B, T, H, P] (H heads of P channels), dt [B, T, H] (Δ, after its
    softplus), a [H] (negative), b and c [B, T, N] (one group, shared by
    the heads) → y [B, T, H, P], from a zero state at t = 0.  T that is
    no multiple of `chunk` is padded with Δ = 0: a padded position
    neither decays the state nor adds to it.  Says what engaged
    (`iotml_ssd_*`, at trace time)."""
    B, T, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    C = (T + pad) // Q
    _record(Q, C, H * P * N * x.dtype.itemsize)
    x = x.reshape(B, C, Q, H, P)
    b, c = b.reshape(B, C, Q, N), c.reshape(B, C, Q, N)
    dt = dt.reshape(B, C, Q, H)
    xd = x * dt[..., None]                              # Δ_t x_t
    # cum[t] = Σ_{r≤t} Δ_r a within the chunk, heads ahead of time
    cum = jnp.cumsum((dt * a).transpose(0, 1, 3, 2), axis=-1)  # [B,C,H,Q]

    with jax.named_scope("ssd_diag"):
        # position s reaches position t ≥ s of its chunk decayed by
        # exp(cum_t - cum_s); masked BEFORE the exp
        seg = cum[..., :, None] - cum[..., None, :]      # [B,C,H,Q(t),Q(s)]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        scores = jnp.einsum("bctn,bcsn->bcts", c, b)     # shared by the heads
        y = jnp.einsum("bchts,bcshp->bcthp", scores[:, :, None] * decay, xd)

    with jax.named_scope("ssd_state"):
        # what each chunk leaves behind: every position decayed to the
        # chunk's end
        to_end = jnp.exp(cum[..., -1:] - cum)            # [B,C,H,Q]
        left = jnp.einsum("bchs,bcshp,bcsn->bchpn", to_end, xd, b)
        # carried across chunks: the state entering chunk z is Σ_{k<z}
        # of chunk k's, decayed over the chunks between
        total = jnp.cumsum(cum[..., -1], axis=1)         # [B,C,H]
        upto = jnp.pad(total, ((0, 0), (1, 0), (0, 0)))  # [B,C+1,H]
        hop = upto[:, :-1, None] - upto[:, None, 1:]     # [B,C(z),C(k),H]
        before = jnp.tril(jnp.ones((C, C), bool), -1)[..., None]
        carry = jnp.exp(jnp.where(before, hop, -jnp.inf))
        entering = jnp.einsum("bzkh,bkhpn->bzhpn", carry, left)

    with jax.named_scope("ssd_off"):
        # the entering state read through C, decayed to each position
        y = y + jnp.einsum("bctn,bchpn,bcht->bcthp", c, entering,
                           jnp.exp(cum))
    return y.reshape(B, C * Q, H, P)[:, :T]


def _record(chunk: int, chunks: int, state_bytes: int) -> None:
    """Python at trace time, once a compilation: the last traced scan's
    chunking stands (as the flash kernels' geometry does)."""
    from ..obs import metrics as obs_metrics

    obs_metrics.ssd_chunk_size.set(chunk)
    obs_metrics.ssd_chunks.set(chunks)
    obs_metrics.ssd_state_bytes.set(state_bytes)
