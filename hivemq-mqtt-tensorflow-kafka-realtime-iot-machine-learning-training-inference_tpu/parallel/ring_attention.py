"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context design (first-class per the framework charter, SURVEY §2.7/§5):
when a per-car history is too long for one chip's HBM — or when the fleet
batch × sequence product wants more FLOPs than one chip has — the sequence
dimension shards over a mesh axis.  Each device holds a local Q/K/V block
[B, T/n, H, D]; K/V blocks rotate around the ring via `jax.lax.ppermute`
(ICI neighbor exchange, bandwidth-optimal), and every device folds each
arriving block into its online-softmax accumulator (`ops.attention
.blockwise_update` — the same math the flash kernel runs within a chip).
After n-1 hops every query has attended every key with O(T/n) memory and
fully overlapped compute/communication (XLA pipelines the permute against
the einsums).

Causality under rotation: device i starts with KV block i; after s hops it
holds block (i - s) mod n, so global key positions are derived from the hop
counter — no gather, no gaps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from ..ops.attention import blockwise_update, finalize_blockwise


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-device body (runs under shard_map). q,k,v: local [B, Tl, H, D]."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    scale = 1.0 / math.sqrt(D)

    qpos = my * Tl + jnp.arange(Tl)  # global positions of local queries

    # mark the accumulators as device-varying over the seq axis so the scan
    # carry type matches its output (shard_map vma typing)
    vary = lambda x: jax.lax.pcast(  # noqa: E731
        x, (axis_name,), to="varying")
    o0 = vary(jnp.zeros((B, Tl, H, D), jnp.float32))
    m0 = vary(jnp.full((B, H, Tl), -1e30, jnp.float32))
    l0 = vary(jnp.zeros((B, H, Tl), jnp.float32))

    # jax.checkpoint on the hop body: autodiff would otherwise save every
    # hop's [B,H,Tl,Tl] probability block — O(T²/n) per device — exactly the
    # memory wall ring attention exists to avoid.  Rematerializing keeps the
    # backward at O(T/n), the flash-attention recompute strategy across chips.
    @jax.checkpoint
    def hop_update(o, m, l, k_blk, v_blk, s):
        src = (my - s) % n  # which global block this hop's KV is
        kpos = src * Tl + jnp.arange(Tl)
        mask = (qpos[:, None] >= kpos[None, :]) if causal else None
        return blockwise_update(o, m, l, q.astype(jnp.float32),
                                k_blk.astype(jnp.float32),
                                v_blk.astype(jnp.float32), scale, mask)

    # hop 0 consumes the resident KV block; the scan then does exactly the
    # n-1 rotations needed (a rotate-last loop would ppermute a full K+V
    # shard per layer that nothing reads).
    o, m, l = hop_update(o0, m0, l0, k, v, 0)

    def hop(carry, s):
        o, m, l, k_blk, v_blk = carry
        # rotate KV to the right neighbor (receive from the left)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        o, m, l = hop_update(o, m, l, k_blk, v_blk, s)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = jax.lax.scan(hop, (o, m, l, k, v),
                                      jnp.arange(1, n))
    return finalize_blockwise(o, l).astype(q.dtype)


def make_ring_attention(mesh: Mesh, seq_axis: str = "seq",
                        causal: bool = True):
    """Build a sequence-sharded attention fn over `mesh`.

    Returns f(q, k, v) on [B, T, H, D] arrays whose T dim is sharded over
    `seq_axis` (other dims replicated or batch-sharded elsewhere).  Usable
    directly or inside a larger shard_mapped/pjit'd train step.
    """
    body = functools.partial(_ring_attention_local, axis_name=seq_axis,
                             causal=causal)
    spec = P(None, seq_axis, None, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = True):
    """shard_map-body form: call inside an existing shard_map/pjit context
    where q/k/v are already the local sequence shards."""
    return _ring_attention_local(q, k, v, axis_name, causal)
