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
blocks of I + A, nilpotent parts of degree SUB, by the product
`(I − A)(I + A²)(I + A⁴)…`, then pairs of blocks merged,
`[[a, 0], [−d A₂₁ a, d]]`, up to the chunk — in float32 on the VPU with
the chunks on the lanes: the MXU would load a tile of weights for every
`16 × 16` product.

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


def _segment(S, x, a_log, dt_bias, chunk: int, sub: int):
    """One segment's chunks: S `[b, h, K, V]` the state the segment
    before it left, x the segment's (q, k, v, f, β) `[b, L, h, ·]` as
    the mixer made them and whether it carries that state on (0.0: a
    window starts here) → (the state that leaves it, o `[b, L, h, V]`)."""
    q, k, v, f, beta, carries = x
    q, k, g = rule_inputs(q, k, f, a_log, dt_bias)
    S = S * carries
    b, L, h, K = k.shape
    n, V = L // chunk, v.shape[-1]
    q, k, v, g = (a.reshape(b, n, chunk, h, a.shape[-1])
                  for a in (q, k, v, g))
    beta = beta.reshape(b, n, chunk, h)
    with jax.named_scope("kda_gates"):
        G = jnp.cumsum(g, axis=2)
        fall = jnp.exp(G)                             # e^{G_r}: into the chunk
        total = fall[:, :, -1]                        # e^{G_C} [b, n, h, K]
        to_end = jnp.exp(G[:, :, -1:] - G)            # e^{G_C − G_s}
    with jax.named_scope("kda_intra"):
        kk, qk = _decayed_scores(q, k, G, sub)
        strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        beta = jnp.moveaxis(beta, 2, 3)                   # [b, n, h, C]
        a = jnp.where(strictly, kk * beta[..., None], 0.0)    # β_r rows
        # (I + A)⁻¹ with the chunks on the lanes, then Diag(β) on its right
        inv = unit_lower_inverse(
            jnp.moveaxis(a.reshape(b * n * h, chunk, chunk), 0, 2), sub)
        t = jnp.moveaxis(inv, 2, 0).reshape(b, n, h, chunk, chunk) \
            * beta[:, :, :, None, :]
        w = jnp.einsum("bnhrs,bnshc->bnrhc", t, k * fall)
        u = jnp.einsum("bnhrs,bnshe->bnrhe", t, v)
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
    _record(chunk, chunks, segments * B * H * K * V * 4)
    dtype = v.dtype
    pad = segments * L - T
    cut = lambda a: jnp.pad(  # noqa: E731
        a.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
    ).reshape((B * segments, 1, L) + a.shape[2:])
    # a window's first segment starts from zero, whatever the last left
    carries = jnp.tile(jnp.arange(segments) > 0, B).astype(jnp.float32)
    body = jax.checkpoint(
        lambda S, x: _segment(S, x, a_log, dt_bias, chunk, sub))
    _, o = jax.lax.scan(body, jnp.zeros((1, H, K, V), jnp.float32),
                        tuple(cut(a) for a in (q, k, v, f, beta))
                        + (carries,))
    return o.reshape(B, segments * L, H, V)[:, :T].astype(dtype)


def _record(chunk: int, chunks: int, state_bytes: int) -> None:
    """Python at trace time, once a compilation: the last traced scan's
    chunking stands (as `ops.ssd._record`'s)."""
    from ..obs import metrics as obs_metrics

    obs_metrics.kda_chunk_size.set(chunk)
    obs_metrics.kda_chunks.set(chunks)
    obs_metrics.kda_state_bytes.set(state_bytes)
