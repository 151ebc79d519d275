"""The gated delta rule with a channel-wise decay (Kimi Delta Attention),
chunked: the second chunked sequence op beside `ops.ssd.ssd_scan`.

The recurrence, a head (k_t, q_t K-vectors, v_t a V-vector, g_t ≤ 0 a
K-vector of log-decays, α_t = exp(g_t), β_t in [0, 1] a scalar, S a K×V
state, S_0 = 0):

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ     o_t = S_tᵀ q_t

Each position first lets every channel of the state decay by its own
factor, then REMOVES what the state holds along k_t (a rank-one
correction) and writes β_t k_t v_tᵀ in its place.  `ssd_scan` cannot be
bent to it: there a chunk's decay is one `[Q, Q]` matrix a head over a
score the heads share; here it is a vector a head and position, and the
correction couples a chunk's positions through the inverse of a unit
lower-triangular matrix (the WY / UT form).

`kda_scan` computes the same o in chunks of C positions.  With
`G_r = Σ_{i≤r} g_i` a channel inside the chunk,

    A = tril₋₁(Diag(β) (K ⊙ e^G)(K ⊙ e^−G)ᵀ)      T = (I + A)⁻¹ Diag(β)
    W = T (K ⊙ e^G)      U = T V      Ṽ = U − W S        (S enters the chunk)
    O = (Q ⊙ e^G) S + tril((Q ⊙ e^G)(K ⊙ e^−G)ᵀ) Ṽ
    S ← Diag(e^{G_C}) S + (K ⊙ e^{G_C − G})ᵀ Ṽ

**`e^−G` is never formed**: a channel's G passes −88 inside one chunk
and its inverse overflows float32.  Every decay is `exp(G_r − G_s)`,
r ≥ s, an exponent ≤ 0, formed in one of two ways.  A chunk is cut in
sub-blocks of `SUB` positions.  Between two sub-blocks i > j the
exponent is split about the point R_i just ahead of sub-block i,
`(G_r − R_i) + (R_i − G_s)`: both parts ≤ 0, so the two factors are in
(0, 1], each scales one operand, and the block is one product on the
MXU (a factor that underflows to 0 stands for a decay below 1e-38).
Inside a sub-block no such point exists, and the `SUB × SUB × K`
differences are masked to r ≥ s BEFORE the exponential, as `ssd_scan`
masks its own, and summed over the channels on the VPU.

The inverse is by substitution in blocks: the `SUB × SUB` diagonal
blocks of I + A first, then pairs of blocks merged, `[[a, 0], [−d A₂₁ a,
d]]`, up to the chunk — in float32 (the plain form, `unit_lower_inverse`:
the diagonal blocks by the product `(I − A)(I + A²)(I + A⁴)…`, all of it
on the VPU with the chunks on the lanes).

**The inner part — the decayed scores, A, the inverse, W and U — is two
Pallas kernels**, `iotml_kda_intra_fwd` and `iotml_kda_intra_bwd` under
one `jax.custom_vjp` (`kda_intra`), where the shapes can be tiled
(`intra_geometry`; else, and as what the tests hold the kernels to, the
plain jnp form `intra_plain`, which XLA makes a dozen fusions a segment
of with `[C, C]` blocks through HBM between them).  A grid step holds
128 positions (whole chunks) of a group of heads, read where they lie in
the segment's `[b, L, H·K]` arrays; a head's `[128, K]` tiles of q, k
and G are turned once, channels on the sublanes and across registers,
positions on the lanes.  There a position meets the one before it by a
lane roll of one, and the decay over d positions inside a sub-block is
the RUNNING PRODUCT of the decay a position, `e^{G_r − G_{r−1}}` (an
exponent ≤ 0, the only exponential the masked part takes): what r meets
d back, `k_{r−d} e^{G_r − G_{r−d}}`, is what it met d − 1 back moved one
lane on, times that decay — a roll and a product a channel tile and
distance, no `[SUB, SUB, K]` differences and no lane reduction, the sum
over the channels register adds: the scores inside sub-blocks come out
BY DIAGONALS, rows of 128 positions (masked where r − d leaves the
sub-block).  The diagonal
blocks' inverse is substitution on those diagonals (`X_d = −Σ_{e<d} X_e
⊙ a_{d−e}` moved e on, rows on the VPU); a lane gather turns diagonals
into `[128, 128]` matrices (and, in the backward, cotangents back); the
scores between sub-blocks, W and U are MXU products with the operands
rounded to bfloat16 exactly where XLA's default rounds them, the merges
of the inverse (and `−Mᵀ dM Mᵀ` back through it) MXU products in float32
at the highest precision.  What nothing but such a product reads — V
into the calls, W out of the forward and so its cotangent, U's
cotangent — crosses the call in bfloat16 where the products round to it
(`_narrow`), as XLA stores what only its own default products read.
The backward keeps nothing of its own: it makes the scores and the
inverse again from the five operands the segment's recomputed body
holds anyway.

The chain over a window's chunks is sequential (a chunk's Ṽ needs the
state that enters it); everything else is batched over the chunks.  To
bound what lives at once, a window is walked in SEGMENTS of `SEGMENT`
chunks by an outer `lax.scan` whose body is recomputed in the backward
pass (`jax.checkpoint`): the forward keeps the state entering each
segment and nothing else, and `jax.grad` of it is the chunked backward —
a segment's products again, then their transposes.  The windows of a
batch are walked one after the other by the SAME scan, the state set to
zero where a window starts: `[B, T, …]` is then `[B · segments, L, …]`
as it lies in memory, where segments ahead of the batch would be a
transposing copy of every operand, the result and their cotangents.  From a
zero state at the window's start; no final state is returned and none is
taken (carrying it on is ROADMAP M2).

What the rule reads is made of what a KDA mixer hands in INSIDE the
segments' recomputed body (`rule_inputs`: the L2 norms of q and k, the
query's scale, the gate's softplus), so the normed q and k and the
decay, and in the backward pass their cotangents, never exist at the
window's size (1.5 GB a layer at the listed cell's).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

#: positions of a sub-block: what the masked differences are formed over
SUB = 16
#: chunks a segment holds: what is batched, and recomputed, at once
SEGMENT = 16


def rule_inputs(q, k, f, a_log, dt_bias):
    """(q, k, g) as the rule reads them, position by position, of what
    a KDA mixer makes `[…, H, K]`: q and k each divided by its L2 norm
    over a head's K features (1e-6 inside the root), q scaled by K^-½,
    and the log-decay `g = −exp(a_log) · softplus(f + dt_bias)` ≤ 0, of
    the gate's `f`, `a_log` `[H]` and `dt_bias` `[H, K]`."""
    with jax.named_scope("kda_norm"):
        q, k = (a * jax.lax.rsqrt(
            jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
            for a in (q, k))
    with jax.named_scope("kda_gates"):
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)
    return q * q.shape[-1] ** -0.5, k, g


def kda_reference(q, k, v, g, beta):
    """The recurrence above, stepped position by position, of what the
    rule READS (`rule_inputs`' q, k and g `[B, T, H, K]`, v `[B, T, H,
    V]`, β `[B, T, H]`): what the tests hold `kda_scan` to."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        held = jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - held)[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    B, _, H, K = q.shape
    S0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0)
        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _mm(a, b):
    """[…, m, k, N] × […, k, n, N] → […, m, n, N] on the VPU: the batch
    N on the lanes, the contraction a run of multiply-adds."""
    return jnp.sum(a[..., :, :, None, :] * b[..., None, :, :, :], axis=-3)


def unit_lower_inverse(a, sub: int):
    """(I + a)⁻¹ for a `[C, C, N]`, N strictly lower-triangular matrices
    with the batch LAST, C a power-of-two multiple of `sub`."""
    C = a.shape[0]
    P = C // sub
    # the diagonal blocks [P, sub, sub, N]: Σ_k (−a)^k as a product
    blocks = jnp.stack([a[p * sub:(p + 1) * sub, p * sub:(p + 1) * sub]
                        for p in range(P)])
    eye = jnp.eye(sub, dtype=a.dtype)[:, :, None]
    inv, power, span = eye - blocks, blocks, 2
    while span < sub:
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
        span *= 2
    n = sub
    while n < C:
        # pairs of neighbouring blocks: [[a, 0], [−d A21 a, d]]
        first, second = inv[0::2], inv[1::2]
        below = jnp.stack([a[(2 * p + 1) * n:(2 * p + 2) * n,
                             2 * p * n:(2 * p + 1) * n]
                           for p in range(C // n // 2)])
        corner = -_mm(second, _mm(below, first))
        inv = jnp.concatenate(
            [jnp.concatenate([first, jnp.zeros_like(first)], axis=-2),
             jnp.concatenate([corner, second], axis=-2)], axis=-3)
        n *= 2
    return inv[0]


def _decayed_scores(q, k, G, sub: int):
    """(Σ_c k_r k_s e^{G_r − G_s}, Σ_c q_r k_s e^{G_r − G_s}) for r ≥ s,
    `[b, n, h, C, C]` each and 0 above the diagonal, of q, k, G
    `[b, n, C, h, K]`: no exponent above 0 is formed."""
    b, n, C, h, K = k.shape
    I = C // sub
    cut = lambda a: a.reshape(b, n, I, sub, h, K)  # noqa: E731
    q5, k5, G5 = cut(q), cut(k), cut(G)
    # inside a sub-block: the differences, masked ahead of the exponential
    lower = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(
        lower, G5[:, :, :, :, None] - G5[:, :, :, None, :], -jnp.inf))
    met = k5[:, :, :, None, :] * decay                # k_s e^{G_r − G_s}
    inside = [jnp.sum(a[:, :, :, :, None] * met, axis=-1)
              for a in (k5, q5)]                      # [b, n, I, r, s, h]
    eye = jnp.eye(I, dtype=k.dtype)
    inside = [jnp.einsum("bnirsh,ij->bnhirjs", a, eye).reshape(b, n, h, C, C)
              for a in inside]
    if I == 1:
        return inside
    # between sub-blocks i > j: about R_i, what G holds just ahead of i
    ahead = G5[:, :, :-1, -1]                         # [b, n, I−1, h, K]
    left = jnp.exp(G5[:, :, 1:] - ahead[:, :, :, None])
    before = (jnp.arange(C)[None, :]
              < (jnp.arange(1, I) * sub)[:, None])[:, :, None, None]
    right = k[:, :, None] * jnp.exp(jnp.where(
        before, ahead[:, :, :, None] - G[:, :, None], -jnp.inf))
    both = jnp.concatenate([k5[:, :, 1:] * left, q5[:, :, 1:] * left], axis=3)
    across = jnp.einsum("bnirhc,bnishc->bnhirs", both, right)
    across = jnp.pad(across, ((0, 0),) * 3 + ((1, 0), (0, 0), (0, 0)))
    return [inside[j] + across[:, :, :, :, j * sub:(j + 1) * sub]
            .reshape(b, n, h, C, C) for j in (0, 1)]


def intra_plain(q, k, G, v, beta, sub: int):
    """A chunk's inner part in plain jnp, what the kernels are held to:
    of q, k, G `[b, n, C, h, K]` (G the log-decays summed from the
    chunk's start), v `[b, n, C, h, V]` and β `[b, n, C, h]` → (W `[b,
    n, C, h, K]`, U `[b, n, C, h, V]`, the queries' scores `[b, n, h, C,
    C]`)."""
    b, n, chunk, h, _ = k.shape
    kk, qk = _decayed_scores(q, k, G, sub)
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    beta = jnp.moveaxis(beta, 2, 3)                   # [b, n, h, C]
    a = jnp.where(strictly, kk * beta[..., None], 0.0)    # β_r rows
    # (I + A)⁻¹ with the chunks on the lanes, then Diag(β) on its right
    inv = unit_lower_inverse(
        jnp.moveaxis(a.reshape(b * n * h, chunk, chunk), 0, 2), sub)
    t = jnp.moveaxis(inv, 2, 0).reshape(b, n, h, chunk, chunk) \
        * beta[:, :, :, None, :]
    w = jnp.einsum("bnhrs,bnshc->bnrhc", t, k * jnp.exp(G))
    u = jnp.einsum("bnhrs,bnshe->bnrhe", t, v)
    return w, u, qk


# ---------------------------------------------------------------------------
# The inner part as two Pallas kernels

#: the kernels' names in a device trace and in the HLO
KDA_FWD_KERNEL = "iotml_kda_intra_fwd"
KDA_BWD_KERNEL = "iotml_kda_intra_bwd"

#: lanes of a vector register: the positions a grid step holds on the chip
_LANES = 128
#: bytes of a grid step's blocks, twice buffered, counted as the
#: backward's eleven `[rows, 128]` blocks a head in float32 (1.4 MiB, so
#: eight heads a step; three of them are bfloat16 where `_narrow` is)
_STEP_BYTES = 12 * 2 ** 20
#: what a call may take of VMEM: the blocks, the backward's scratch (nine
#: `[128, 128]` buffers and two of `[SUB, 128, 128]`: 2.6 MiB) and what a
#: head's `[128, 128]` temporaries spill
_VMEM_BYTES = 40 * 2 ** 20

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


class IntraGeometry(NamedTuple):
    """How the kernels tile a segment: `rows` positions (whole chunks)
    and `heads` heads a grid step."""
    rows: int
    heads: int


def intra_geometry(L: int, H: int, K: int, V: int, chunk: int,
                   interpret: bool) -> Optional[IntraGeometry]:
    """The kernels' tiling of a segment of L positions in chunks of
    `chunk`, H heads of K keys and V values — or None where they cannot
    take it and the plain form runs: a chunk under the sublane tile of
    8 or over the 128 lanes a step's positions lie on; and, compiled
    (the interpreter has no tiles), positions that do not fill whole
    128-lane steps or heads that are no whole 128-lane tiles.  A step
    holds the most whole chunks that fit 128 positions, and the most
    heads — a divisor of H, a multiple of the sublane tile where H is
    not taken whole — whose blocks fit `_STEP_BYTES`."""
    n = L // chunk
    if chunk < 8 or chunk > _LANES or n * chunk != L:
        return None
    per = max(p for p in range(1, n + 1)
              if n % p == 0 and p * chunk <= _LANES)
    rows = per * chunk
    if not interpret and (rows != _LANES or K % _LANES or V % _LANES):
        return None
    a_head = 2 * 4 * rows * (7 * K + 3 * V + rows)
    fits = [g for g in range(1, H + 1)
            if H % g == 0 and (g == H or g % 8 == 0 or interpret)
            and g * a_head <= _STEP_BYTES]
    if not fits:
        return None
    return IntraGeometry(rows, max(fits))


def _dot(a, b, dims, exact: bool):
    """A product on the MXU accumulated in float32.  `exact`: float32
    operands at the highest precision; else the operands rounded to
    bfloat16, which is what XLA's default makes of a float32 product on
    the chip — said here because Mosaic's own default is not XLA's."""
    if exact:
        return jax.lax.dot_general(
            a, b, (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (dims, ((), ())),
        preferred_element_type=jnp.float32)


def _grid(R: int):
    """(row index, column index) of an `[R, R]` block."""
    return (jax.lax.broadcasted_iota(jnp.int32, (R, R), 0),
            jax.lax.broadcasted_iota(jnp.int32, (R, R), 1))


def _within(r, s, size: int):
    """Whether r and s lie in one aligned run of `size`, a power of two."""
    return (r ^ s) < size


def _inside(kT, qT, aT, out, sub: int, met=None, kept=None):
    """The scores inside sub-blocks, by diagonals, into the scratches
    `out` (kk's, and qk's where there are two): row d holds `Σ_c k_r
    k_{r−d} e^{G_r − G_{r−d}}` at position r, 0 where r − d leaves r's
    sub-block.  Of the refs kT, qT, aT `[K, R]` — k, q and the decay a
    position `α = e^{G_r − G_{r−1}}` (`_decays_a_position`), the channels
    on the sublanes and across registers, the step's positions on the
    lanes — so a position meets the one before it by a lane roll of ONE
    and the sum over the channels is register adds.  The decay over d
    positions is the running product of α, never an exponential of its
    own (and no exponent above 0 is formed): what position r meets at
    distance d, `ψ_d[r] = k_{r−d} e^{G_r − G_{r−d}}`, is `ψ_{d−1}` moved
    one lane on, times α — a roll and a product a channel tile and
    distance, in the scratch `met`.  A trip of the loop is one distance
    over all the channel tiles: independent chains, two registers of
    sums a row.  The backward wants the two factors apart, and again:
    with `kept` = (decays, earlier) `[sub, K, R]` each, `e_d = (e_{d−1}
    moved on) ⊙ α` and `k_{r−d}` are left there for every d."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, R = kT.shape
    ct = _channel_tiles(K)[0]
    tiles = [slice(t * ct, (t + 1) * ct) for t in range(K // ct)]
    local = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) & (sub - 1)

    def sums(d, met_of):
        """Rows of the sums over channels of k ⊙ met and q ⊙ met, met
        what a tile's channels meet d positions back."""
        parts = [[jnp.zeros((ct, R), jnp.float32)] * 2 for _ in out]
        for t, rows in enumerate(tiles):
            psi = met_of(rows)
            for i, ref in enumerate((kT, qT)[:len(out)]):
                parts[i][t % 2] = parts[i][t % 2] + ref[rows, :] * psi
        for i, (a, b) in enumerate(parts):
            out[i][pl.ds(d, 1), :] = jnp.where(
                local >= d, jnp.sum(a + b, axis=0, keepdims=True), 0.0)

    def start(rows):
        if kept is None:
            met[rows, :] = kT[rows, :]
        else:
            kept[0][0, rows, :] = jnp.ones((ct, R), jnp.float32)
            kept[1][0, rows, :] = kT[rows, :]
        return kT[rows, :]

    def step(d):
        def psi(rows):
            if kept is None:
                met[rows, :] = pltpu.roll(met[rows, :], 1, 1) * aT[rows, :]
                return met[rows, :]
            decays, earlier = kept
            decays[d, rows, :] = pltpu.roll(decays[d - 1, rows, :], 1, 1) \
                * aT[rows, :]
            earlier[d, rows, :] = pltpu.roll(earlier[d - 1, rows, :], 1, 1)
            return earlier[d, rows, :] * decays[d, rows, :]
        return psi

    sums(0, start)

    for d in range(1, sub):
        sums(d, step(d))


def _decays_a_position(G):
    """`α = e^{G_r − G_{r−1}}` of a tile `[channels, R]` of G, positions
    on the lanes: the decay a position.  Inside a chunk G only falls;
    where r − 1 is not in r's chunk the exponent is held at 0 — no
    exponent above 0 is formed — and no score reads that α."""
    from jax.experimental.pallas import tpu as pltpu

    return jnp.exp(jnp.minimum(G - pltpu.roll(G, 1, 1), 0.0))


#: channel tiles a trip of the backward's loop over them takes: their
#: chains are independent, so the rolls' latencies overlap
_TILES_A_TRIP = 4


def _channel_tiles(K: int):
    """(channels of a tile, tiles a trip of the backward's loop) of a
    head's K channels: a register of 8 sublanes where K has whole ones."""
    ct = 8 if K % 8 == 0 else K
    return ct, _TILES_A_TRIP if (K // ct) % _TILES_A_TRIP == 0 else 1


def _between(k, q, G, chunk: int, sub: int, exact: bool):
    """The scores between sub-blocks i > j of a chunk, `[R, R]` each for
    k's rows and q's and 0 elsewhere: about R_i, what G holds just ahead
    of sub-block i, both factors in (0, 1] — products on the MXU."""
    R, K = k.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    empty = jnp.zeros((sub, R), jnp.float32)
    kk, qk = [], []
    for c0 in range(0, R, chunk):
        kk.append(empty), qk.append(empty)
        for r0 in range(c0 + sub, c0 + chunk, sub):
            both, right = _about(k, q, G, c0, r0, chunk, sub, at)[:2]
            across = _dot(both, right, _NT, exact)       # [2 sub, R]
            kk.append(across[:sub]), qk.append(across[sub:])
    return jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0)


def _about(k, q, G, c0: int, r0: int, chunk: int, sub: int, at):
    """The two operands of a between-sub-blocks product, split about
    what G holds at r0 − 1: (k's and q's rows of the sub-block at r0
    scaled by e^{G_r − R}, `[2 sub, K]`; the chunk's keys ahead of r0
    scaled by e^{R − G_s}, `[R, K]` with 0 rows elsewhere), and the two
    decays alone."""
    R = k.shape[0]
    ahead = G[r0 - 1:r0]
    left = jnp.exp(G[r0:r0 + sub] - ahead)
    both = jnp.concatenate([k[r0:r0 + sub] * left, q[r0:r0 + sub] * left],
                           axis=0)
    decay = jnp.exp(jnp.where(at < r0 - c0, ahead - G[c0:c0 + chunk],
                              -jnp.inf))
    return both, _placed(k[c0:c0 + chunk] * decay, c0, R, 0), left, decay


def _placed(block, at: int, size: int, axis: int):
    """`block` from `at` on along `axis` of zeros `size` long there."""
    def zeros(n):
        shape = list(block.shape)
        shape[axis] = n
        return [jnp.zeros(shape, block.dtype)] * (n > 0)

    return jnp.concatenate(
        zeros(at) + [block] + zeros(size - at - block.shape[axis]), axis=axis)


def _matrix_of(diag, sub: int):
    """`[R, R]` with row d of the scratch `diag` on diagonal d of every
    sub-block (entry (r, r − d)) and 0 elsewhere: the rows turned, and
    each position's row gathered along the lanes."""
    r, s = _grid(diag.shape[0])
    turned = jnp.take_along_axis(diag[...].T, jnp.maximum(r - s, 0), axis=1)
    return jnp.where(_within(r, s, sub) & (r >= s), turned, 0.0)


def _to_diagonals(matrix, diag, sub: int):
    """`_matrix_of` back: row d of `diag` is then diagonal d of the
    sub-blocks of `matrix`, for d under `sub`."""
    r, d = _grid(matrix.shape[0])
    along = jnp.take_along_axis(matrix, jnp.maximum(r - d, 0), axis=1)
    diag[...] = jnp.where((d < sub) & (d <= (r & (sub - 1))), along, 0.0).T


def _column(row):
    """`[1, R]` → `[R, 1]`."""
    return jnp.broadcast_to(row, (8, row.shape[1])).T[:, :1]


def _inverse(kk_rows, kk_between, brow, bcol, diag, chunk: int, sub: int):
    """(I + A)⁻¹ `[R, R]` of the step's chunks, A = tril₋₁(β_r kk), in
    float32, of kk's diagonals inside sub-blocks (the scratch
    `kk_rows`) and its entries between them.  The sub-blocks on the
    diagonal by substitution on their diagonals, `X_d = −Σ_{e<d} X_e ⊙
    (a_{d−e} moved e on)` — rows of R positions on the VPU, left in the
    scratch `diag`; then pairs of blocks merged up to the chunk, `[[a,
    0], [−d A₂₁ a, d]]`, products on the MXU at the highest precision."""
    from jax.experimental.pallas import tpu as pltpu

    a = [None] + [brow * kk_rows[d:d + 1, :] for d in range(1, sub)]
    inv = [jnp.ones_like(brow)]
    for d in range(1, sub):
        held = a[d]
        for e in range(1, d):
            held = held + inv[e] * pltpu.roll(a[d - e], e, 1)
        inv.append(-held)
    for d, row in enumerate(inv):
        diag[d:d + 1, :] = row
    m = _matrix_of(diag, sub)
    if kk_between is None:
        return m
    R = m.shape[0]
    r, s = _grid(R)
    lower = bcol * kk_between
    n = sub
    while n < chunk:
        # only the second block of a pair has a corner: its rows alone
        # go through the two products, half of each left operand
        pairs = range(0, R, 2 * n)

        def seconds(x, n=n, pairs=pairs):
            return jnp.concatenate([x[at + n:at + 2 * n] for at in pairs],
                                   axis=0)

        def back(x, n=n, pairs=pairs):
            zero = jnp.zeros((n, R), jnp.float32)
            return jnp.concatenate(
                [part for i in range(len(pairs))
                 for part in (zero, x[i * n:(i + 1) * n])], axis=0)

        below = jnp.where(_within(r, s, 2 * n) & ~_within(r, s, n) & (r > s),
                          lower, 0.0)
        inner = back(_dot(seconds(below), m, _NN, True))
        m = m - back(_dot(seconds(m), inner, _NN, True))
        n *= 2
    return m


def _head(j, K: int, V: int):
    """The lanes of head j of a step's blocks: its keys, its values."""
    from jax.experimental import pallas as pl

    return tuple(pl.ds(pl.multiple_of(j * n, n), n) for n in (K, V))


def _intra_fwd_step(q_ref, k_ref, g_ref, v_ref, bt_ref, w_ref, u_ref, qk_ref,
                    kT, qT, aT, met, diag, rows_k, rows_q, *, heads: int,
                    chunk: int, sub: int, exact: bool):
    """Grid step (window, rows, head group): W, U and the queries'
    scores of `heads` heads over the step's rows, a head at a time."""
    from jax.experimental import pallas as pl

    R = q_ref.shape[1]
    K, V = q_ref.shape[2] // heads, v_ref.shape[2] // heads

    def head(j, _):
        ck, cv = _head(j, K, V)
        q, k, G = q_ref[0, :, ck], k_ref[0, :, ck], g_ref[0, :, ck]
        kT[...], qT[...], aT[...] = k.T, q.T, _decays_a_position(G.T)
        brow = bt_ref[0, pl.ds(j, 1), :]
        _inside(kT, qT, aT, (rows_k, rows_q), sub, met=met)
        kk_b = qk_b = None
        if chunk > sub:
            kk_b, qk_b = _between(k, q, G, chunk, sub, exact)
        t = _inverse(rows_k, kk_b, brow, _column(brow), diag, chunk, sub) \
            * brow
        w_ref[0, :, ck] = _dot(t, k * jnp.exp(G), _NN, exact).astype(
            w_ref.dtype)
        u_ref[0, :, cv] = _dot(t, v_ref[0, :, cv], _NN, exact)
        qk = _matrix_of(rows_q, sub)
        if qk_b is not None:
            qk = qk + qk_b
        for p, at in enumerate(range(0, R, chunk)):
            qk_ref[0, p, j] = qk[at:at + chunk, at:at + chunk]
        return 0

    jax.lax.fori_loop(0, heads, head, 0)


def _intra_bwd_step(q_ref, k_ref, g_ref, v_ref, bt_ref, dw_ref, du_ref,
                    dqk_ref, dq_ref, dk_ref, dg_ref, dv_ref, db_ref,
                    kT, qT, aT, dkT, dqT, dgT, diag, diag_k, diag_q, decays,
                    earlier, *,
                    heads: int, chunk: int, sub: int, exact: bool):
    """Grid step (window, rows, head group): the cotangents of q, k, G,
    v and β (a row a head) from those of W, U and the scores; the scores
    and the inverse made again from the step's inputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R = q_ref.shape[1]
    K, V = q_ref.shape[2] // heads, v_ref.shape[2] // heads
    ct, per = _channel_tiles(K)
    r, s = _grid(R)
    causal = _within(r, s, chunk) & (r >= s)
    place = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def head(j, _):
        ck, cv = _head(j, K, V)
        q, k, G, v = (q_ref[0, :, ck], k_ref[0, :, ck], g_ref[0, :, ck],
                      v_ref[0, :, cv])
        dw, du = dw_ref[0, :, ck], du_ref[0, :, cv]
        kT[...], qT[...], aT[...] = k.T, q.T, _decays_a_position(G.T)
        brow = bt_ref[0, pl.ds(j, 1), :]
        bcol = _column(brow)
        _inside(kT, qT, aT, (diag_q,), sub, kept=(decays, earlier))
        kk_b = _between(k, q, G, chunk, sub, exact)[0] if chunk > sub \
            else None
        m = _inverse(diag_q, kk_b, brow, bcol, diag, chunk, sub)
        t = m * brow
        fall = jnp.exp(G)
        # W = T (K ⊙ e^G), U = T V
        dt = jnp.where(causal, _dot(dw, k * fall, _NT, exact)
                       + _dot(du, v, _NT, exact), 0.0)
        dfell = _dot(t, dw, _TN, exact)                  # d(K ⊙ e^G)
        dv_ref[0, :, cv] = _dot(t, du, _TN, exact)
        # T = M Diag(β), M = (I + A)⁻¹, A = tril₋₁(β_r kk)
        da = -jnp.where(causal & (r > s), _dot(
            _dot(m, dt * brow, _TN, True), m, _NT, True), 0.0)
        kk = _matrix_of(diag_q, sub)
        if kk_b is not None:
            kk = kk + kk_b
        db_ref[0, pl.ds(j, 1), :] = \
            jnp.sum(dt * m, axis=0, keepdims=True) \
            + jnp.sum((da * kk).T, axis=0, keepdims=True)
        dkk = da * bcol
        dqk = jnp.where(causal, jnp.concatenate(
            [_placed(dqk_ref[0, p, j], c0, R, 1)
             for p, c0 in enumerate(range(0, R, chunk))], axis=0), 0.0)
        # K ⊙ e^G's share, then the scores between sub-blocks: the
        # forward's products turned, added onto their rows of the blocks
        dk_ref[0, :, ck] = dfell * fall
        dq_ref[0, :, ck] = jnp.zeros_like(k)
        dg_ref[0, :, ck] = dfell * k * fall
        for c0 in range(0, R, chunk):
            whole = slice(c0, c0 + chunk)
            for r0 in range(c0 + sub, c0 + chunk, sub):
                here = slice(r0, r0 + sub)
                both, right, left, decay = _about(k, q, G, c0, r0, chunk,
                                                  sub, place)
                d_both = jnp.concatenate([dkk[here], dqk[here]], axis=0)
                d_left = _dot(d_both, right, _NN, exact)        # [2 sub, K]
                by_k, by_q = d_left[:sub] * left, d_left[sub:] * left
                dk_ref[0, here, ck] += by_k
                dq_ref[0, here, ck] += by_q
                dg_ref[0, here, ck] += by_k * k[here] + by_q * q[here]
                d_right = _dot(d_both, both, _TN, exact)[whole] * decay
                dk_ref[0, whole, ck] += d_right
                dg_ref[0, whole, ck] -= d_right * k[whole]
        # the scores inside sub-blocks, by diagonals
        _to_diagonals(dkk, diag_k, sub)
        _to_diagonals(dqk, diag_q, sub)

        def tiles(t_, _):
            for u in range(per):
                one_tile(pl.ds(pl.multiple_of((t_ * per + u) * ct, ct), ct))
            return 0

        def one_tile(rows):
            k_, q_ = kT[rows, :], qT[rows, :]
            by_k = by_q = back = to_g = jnp.zeros((ct, R), jnp.float32)
            for d in range(sub):
                bk, bq = diag_k[d:d + 1, :], diag_q[d:d + 1, :]
                pair = bk * k_ + bq * q_
                if not d:
                    by_k, by_q, back = by_k + bk * k_, by_q + bq * k_, pair
                    continue
                # what `_inside` kept; the rows of dkk, dqk are masked
                e, met = decays[d, rows, :], earlier[d, rows, :]
                by_k, by_q = by_k + bk * (met * e), by_q + bq * (met * e)
                # a pair's share of dG: ONE product, added where r lies
                # and taken off where s does, so that what the gate's
                # running sum makes of the two cancels to the bit
                pair = pair * e
                moved = pltpu.roll(pair, R - d, 1)
                back = back + moved
                to_g = to_g + pair * met - moved * k_
            dkT[rows, :] = by_k + back
            dqT[rows, :] = by_q
            dgT[rows, :] = to_g

        jax.lax.fori_loop(0, K // (ct * per), tiles, 0)
        dk_ref[0, :, ck] += dkT[...].T
        dq_ref[0, :, ck] += dqT[...].T
        dg_ref[0, :, ck] += dgT[...].T
        return 0

    jax.lax.fori_loop(0, heads, head, 0)


def _narrow(exact: bool):
    """What carries an array that nothing but products read — V into the
    kernels, W out of the forward (so its cotangent too), U's cotangent
    into the backward: float32 where the products take float32
    operands, else the bfloat16 they round to anyway, so that XLA keeps
    the stacks around the calls, and the calls their blocks, at half
    the bytes (as it does around its own default products)."""
    return jnp.float32 if exact else jnp.bfloat16


def _intra_specs(geom: IntraGeometry, chunk: int, K: int, V: int):
    """Block specs on grid (windows, row blocks, head groups): a
    `[b, L, H·K]` operand's, a `[b, L, H·V]` one's, the scores' `[b,
    L / chunk, H, chunk, chunk]` and β's `[b, H, L]`."""
    from jax.experimental import pallas as pl

    R, g = geom
    wide = lambda n: pl.BlockSpec(  # noqa: E731
        (1, R, g * n), lambda i, m, j: (i, m, j))
    return wide(K), wide(V), pl.BlockSpec(
        (1, R // chunk, g, chunk, chunk),
        lambda i, m, j: (i, m, j, 0, 0)), pl.BlockSpec(
        (1, g, R), lambda i, m, j: (i, j, m))


def _intra_params():
    """What both calls tell Mosaic: every grid step is its own, and
    the VMEM they may take."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel",) * 3,
                                vmem_limit_bytes=_VMEM_BYTES)


def _intra_cost(b: int, L: int, H: int, K: int, V: int, chunk: int,
                sub: int, back: bool):
    """For XLA's scheduler: a head-chunk's products and its running
    products inside sub-blocks (again, with their transposes, in the
    backward), an exponential a position and channel for the decay, e^G
    and each side of a split, and the operands and results through HBM
    once."""
    from jax.experimental import pallas as pl

    chunks = b * (L // chunk) * H
    products = 2 * chunk * chunk * (3 * K + V) + 4 * chunk ** 3
    inside = chunk * sub * K
    return pl.CostEstimate(
        flops=chunks * (products + 6 * inside) * (1 + 2 * back),
        transcendentals=chunks * 4 * chunk * K * (1 + back),
        bytes_accessed=4 * b * L * H * (3 * K + V + chunk)
        * (1 + back) + 4 * b * L * H * (K + V))


@functools.partial(jax.jit, static_argnames=("chunk", "geom", "interpret",
                                             "exact"))
def _intra_forward(q, k, G, v, beta, chunk: int, geom: IntraGeometry,
                   interpret: bool, exact: bool):
    """(W, U, scores) of `kda_intra`: `iotml_kda_intra_fwd`.  Jitted, as
    `ops.add_rows.add_rows` is and for set-up's sake: a fit calls this
    from every delta-rule layer's forward and its recomputations, and
    under `jax.jit` the kernel is traced once and the module holds one
    function they call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (b, L, _), H = k.shape, beta.shape[2]
    K, V, R = k.shape[2] // H, v.shape[2] // H, geom.rows
    sub = min(SUB, chunk)
    keys, values, scores, rows = _intra_specs(geom, chunk, K, V)
    narrow = _narrow(exact)
    with jax.named_scope("kda_intra"):
        return pl.pallas_call(
            functools.partial(_intra_fwd_step, heads=geom.heads, chunk=chunk,
                              sub=sub, exact=exact),
            name=KDA_FWD_KERNEL, grid=(b, L // R, H // geom.heads),
            in_specs=[keys, keys, keys, values, rows],
            out_specs=[keys, values, scores],
            out_shape=[jax.ShapeDtypeStruct(k.shape, narrow),
                       jax.ShapeDtypeStruct(v.shape, jnp.float32),
                       jax.ShapeDtypeStruct(
                           (b, L // chunk, H, chunk, chunk), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((K, R), jnp.float32)] * 4
            + [pltpu.VMEM((R, R), jnp.float32)] * 3,
            compiler_params=_intra_params(),
            cost_estimate=_intra_cost(b, L, H, K, V, chunk, sub, False),
            interpret=interpret,
        )(q, k, G, v.astype(narrow), jnp.swapaxes(beta, 1, 2))


@functools.partial(jax.jit, static_argnames=("chunk", "geom", "interpret",
                                             "exact"))
def _intra_backward(q, k, G, v, beta, dw, du, dqk, chunk: int,
                    geom: IntraGeometry, interpret: bool, exact: bool):
    """The cotangents of (q, k, G, v, β) of `kda_intra` at those of its
    three results: `iotml_kda_intra_bwd`, jitted as the forward."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (b, L, _), H = k.shape, beta.shape[2]
    K, V, R = k.shape[2] // H, v.shape[2] // H, geom.rows
    sub = min(SUB, chunk)
    keys, values, scores, rows = _intra_specs(geom, chunk, K, V)
    narrow = _narrow(exact)
    with jax.named_scope("kda_intra"):
        dq, dk, dG, dv, db = pl.pallas_call(
            functools.partial(_intra_bwd_step, heads=geom.heads, chunk=chunk,
                              sub=sub, exact=exact),
            name=KDA_BWD_KERNEL, grid=(b, L // R, H // geom.heads),
            in_specs=[keys, keys, keys, values, rows, keys, values, scores],
            out_specs=[keys, keys, keys, values, rows],
            out_shape=[jax.ShapeDtypeStruct(a, jnp.float32)
                       for a in (k.shape,) * 3 + (v.shape, (b, H, L))],
            scratch_shapes=[pltpu.VMEM((K, R), jnp.float32)] * 6
            + [pltpu.VMEM((R, R), jnp.float32)] * 3
            + [pltpu.VMEM((sub, K, R), jnp.float32)] * 2,
            compiler_params=_intra_params(),
            cost_estimate=_intra_cost(b, L, H, K, V, chunk, sub, True),
            interpret=interpret,
        )(q, k, G, v.astype(narrow), jnp.swapaxes(beta, 1, 2), dw,
          du.astype(narrow), dqk)
    return dq, dk, dG, dv, jnp.swapaxes(db, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _intra(q, k, G, v, beta, chunk, geom, interpret, exact):
    return _intra_forward(q, k, G, v, beta, chunk=chunk, geom=geom,
                          interpret=interpret, exact=exact)


def _intra_fwd(q, k, G, v, beta, chunk, geom, interpret, exact):
    _record_kernel("fwd", beta, chunk)
    # through `_intra` again and not `_intra_forward`: a differentiated
    # body then holds the call as ONE opaque equation, and a fit whose
    # passes re-derive it lowers the forward's function three times (a
    # context) and not six (`tests/test_mosaic_aot.py` counts them)
    return _intra(q, k, G, v, beta, chunk, geom, interpret, exact), \
        (q, k, G, v, beta)


def _intra_bwd(chunk, geom, interpret, exact, kept, cotangents):
    _record_kernel("bwd", kept[4], chunk)
    return _intra_backward(*kept, *cotangents, chunk=chunk, geom=geom,
                           interpret=interpret, exact=exact)


_intra.defvjp(_intra_fwd, _intra_bwd)


def kda_intra(q, k, G, v, beta, chunk: int, geom: IntraGeometry):
    """A chunk's inner part by the kernels, of a segment as it lies: q,
    k, G `[b, L, H·K]` (G summed from each chunk's start), v `[b, L,
    H·V]`, β `[b, L, H]` → (W `[b, L, H·K]`, U `[b, L, H·V]`, the
    queries' scores `[b, L / chunk, H, chunk, chunk]`, 0 above the
    diagonal).  W is bfloat16 where its products round to it (`_narrow`),
    else float32.
    One kernel forward and one backward, which keeps the five operands
    and makes the scores and the inverse again.  Off the chip the
    kernels run interpreted (`fused_train.interpret_mode` decides).  A
    product between sub-blocks, W and U round their operands to
    bfloat16 where XLA's default would — compiled, under no
    `jax.default_matmul_precision` — and nowhere else."""
    from .fused_train import interpret_mode

    interpret = interpret_mode()
    exact = interpret or jax.config.jax_default_matmul_precision not in (
        None, "default", "bfloat16", "fastest")
    return _intra(q, k, G, v, beta, chunk, geom, interpret, exact)


def _segment(S, x, a_log, dt_bias, chunk: int, sub: int,
             geom: Optional["IntraGeometry"]):
    """One segment's chunks: S `[b, h, K, V]` the state the segment
    before it left, x the segment's (q, k, v, f, β) `[b, L, h, ·]` as
    the mixer made them and whether it carries that state on (0.0: a
    window starts here) → (the state that leaves it, o `[b, L, h, V]`).
    The inner part by the kernels where `geom` says how, else plain."""
    q, k, v, f, beta, carries = x
    q, k, g = rule_inputs(q, k, f, a_log, dt_bias)
    S = S * carries
    b, L, h, K = k.shape
    n, V = L // chunk, v.shape[-1]
    q, k, v, g = (a.reshape(b, n, chunk, h, a.shape[-1])
                  for a in (q, k, v, g))
    with jax.named_scope("kda_gates"):
        G = jnp.cumsum(g, axis=2)
        fall = jnp.exp(G)                             # e^{G_r}: into the chunk
        total = fall[:, :, -1]                        # e^{G_C} [b, n, h, K]
        to_end = jnp.exp(G[:, :, -1:] - G)            # e^{G_C − G_s}
    with jax.named_scope("kda_intra"):
        if geom is None:
            w, u, qk = intra_plain(q, k, G, v,
                                   beta.reshape(b, n, chunk, h), sub)
        else:
            w, u, qk = kda_intra(*(a.reshape(b, L, -1) for a in (q, k, G, v)),
                                 beta, chunk, geom)
            w, u = w.reshape(k.shape), u.reshape(v.shape)
    with jax.named_scope("kda_state"):
        def chain(S, c):
            w_n, u_n, k_n, total_n = c
            new = u_n - jnp.einsum("brhc,bhce->brhe", w_n, S)
            left = total_n[..., None] * S \
                + jnp.einsum("brhc,brhe->bhce", k_n, new)
            return left, (S, new)

        S, (entering, new) = jax.lax.scan(chain, S, tuple(
            jnp.moveaxis(a, 1, 0) for a in (w, u, k * to_end, total)))
        entering, new = (jnp.moveaxis(a, 0, 1) for a in (entering, new))
    with jax.named_scope("kda_out"):
        o = jnp.einsum("bnrhc,bnhce->bnrhe", q * fall, entering) \
            + jnp.einsum("bnhrs,bnshe->bnrhe", qk, new)
    return S, o.reshape(b, L, h, V)


def kda_scan(q, k, v, f, beta, a_log, dt_bias, chunk: int):
    """The gated delta rule above, chunked, of what a KDA mixer makes.

    q, k `[B, T, H, K]` (ahead of their norms), v `[B, T, H, V]`, f
    `[B, T, H, K]` (the decay gate ahead of its softplus), beta `[B, T,
    H]` in [0, 1], a_log `[H]`, dt_bias `[H, K]` → o `[B, T, H, V]`,
    from a zero state at t = 0; what the rule reads of them is
    `rule_inputs`', made a segment at a time; the state and every decay
    in float32.  `chunk` is a power-of-two multiple of `SUB`, or a power
    of two under it.  T is padded to whole segments with zeros: a padded
    position writes nothing (β = 0), and what its gate makes of f = 0
    decays a state that no kept position reads — the padding lies behind
    the window and no final state is returned.  Says what engaged
    (`iotml_kda_*`, at trace time)."""
    B, T, H, K = k.shape
    V = v.shape[-1]
    sub = min(SUB, chunk)
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk}: a power of two, so that the "
                         f"blocks of the inverse pair up")
    chunks = -(-T // chunk)
    per = min(SEGMENT, chunks)
    L = per * chunk
    segments = -(-chunks // per)
    from .fused_train import interpret_mode

    geom = intra_geometry(L, H, K, V, chunk, interpret_mode())
    _record(chunk, chunks, segments * B * H * K * V * 4, geom is not None)
    dtype = v.dtype
    pad = segments * L - T
    cut = lambda a: jnp.pad(  # noqa: E731
        a.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
    ).reshape((B * segments, 1, L) + a.shape[2:])
    # a window's first segment starts from zero, whatever the last left
    carries = jnp.tile(jnp.arange(segments) > 0, B).astype(jnp.float32)
    body = jax.checkpoint(
        lambda S, x: _segment(S, x, a_log, dt_bias, chunk, sub, geom))
    _, o = jax.lax.scan(body, jnp.zeros((1, H, K, V), jnp.float32),
                        tuple(cut(a) for a in (q, k, v, f, beta))
                        + (carries,))
    return o.reshape(B, segments * L, H, V)[:, :T].astype(dtype)


def _record(chunk: int, chunks: int, state_bytes: int, kernels: bool) -> None:
    """Python at trace time, once a compilation: the last traced scan's
    chunking stands (as `ops.ssd._record`'s).  Where the plain form of
    the inner part runs, the kernels' gauges say 0; else each kernel's
    call says what it covers (`_record_kernel`)."""
    from ..obs import metrics as obs_metrics

    obs_metrics.kda_chunk_size.set(chunk)
    obs_metrics.kda_chunks.set(chunks)
    obs_metrics.kda_state_bytes.set(state_bytes)
    if not kernels:
        for direction in ("fwd", "bwd"):
            obs_metrics.kda_intra_kernel.set(0, direction=direction)


def _record_kernel(direction: str, beta, chunk: int) -> None:
    """The head-chunks a kernel's call covers, of its β `[b, L, H]`."""
    from ..obs import metrics as obs_metrics

    b, L, H = beta.shape
    obs_metrics.kda_intra_kernel.set(b * (L // chunk) * H,
                                     direction=direction)
