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

import jax
import jax.numpy as jnp


def causal_conv1d(x, kernel, bias):
    """Depthwise causal convolution over time: x [B, T, C], kernel
    [K, C], bias [C] → [B, T, C], with y_t = bias + Σ_k kernel[k] ·
    x_{t-(K-1)+k} and x_t = 0 for t < 0 (left-padded with zeros).

    Written as K shifted multiply-adds: a depthwise convolution has no
    contraction for the MXU (2K operations an element against 8 bytes
    moved), so what matters is that XLA fuses the K taps into ONE
    elementwise pass over x.  `lax.conv_general_dilated` with
    `feature_group_count=C` is the same arithmetic as a convolution
    op, which the TPU compiler tiles for the MXU it cannot use here.
    """
    K, T = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = bias + xp[:, :T] * kernel[0]
    for k in range(1, K):
        y = y + xp[:, k:k + T] * kernel[k]
    return y


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
