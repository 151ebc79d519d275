"""Two parts a layer of `models.hybrid.SensorHybrid` may be made of
beside the ones that file holds: a latent-attention mixer and a
sparse-expert feed-forward layer — the DeepSeek-V3-shaped decoder's.

**Latent attention** (multi-head latent attention in its training
form, no query low-rank): with `u` the normed stream, `q = u W_q` is H
heads of `nope + rope` features; `[c, k_pe] = u W_kva` is a latent of
`kv_rank` and ONE rotary key head shared by all H; `c ← RMSNorm(c)`;
`[k_nope, v] = c W_kvb` is H heads of `nope + v`; rotary positions turn
`q_pe` and `k_pe` — or, with `mla_rope` off, turn NOTHING: the
`rope`-wide slice of q and the one shared key head stay where they are,
un-turned, and the layer carries no positions (order reaches it through
the causal mask and the layers beside it); `k = [k_nope, k_pe]`; scores `q kᵀ / √(nope+rope)`,
causal softmax, `o = P v` → `W_o`.  Query/key heads are wider than
value heads (192 beside 128 at the published widths), which the flash
kernels take as they are.  The weight-absorbed form and a latent cache
are serving's and are not here.

**Expert layer:** `y = Σ_k w_k · E_{i_k}(u) + S(u)` — the router's
`top_k` of ALL `experts` (`ops.moe.route`), the terms of the experts
HELD here (`experts_held`, a contiguous range: one chip's share under
expert parallelism; the others' terms are left out, with no exchange
and nothing standing in for them), and a shared expert every token
takes, computed whole (`shared_dim` 0: the model has none, and the
layer is `Σ_k w_k · E_{i_k}(u)` alone).  Dropless
(`ops.moe.experts_apply`).  It reports the assignments every expert
got, a step, through the `reports` collection: data, not shape, so it
comes back with the losses.

More of the layer is data.  The router's **form** (`router_form`,
`ops.moe.ROUTER_FORMS`: sigmoids with a selection-only bias, or the
largest raw logits and a softmax over the selected, which has no bias
and whose tree has no such leaf), and **what it reads** (`router_input`): the normed stream the experts read,
or — `block` — the block's own input, un-normed and ahead of the mixer,
which the block hands in apart: the layer is then called twice, once to
route (`plan_only`) and once, behind the mixer, with that plan.  The
experts' **form** (`expert_form`, `ops.moe.EXPERT_FORMS`: the gated SiLU
above, the same gated by a ReLU, or the non-gated squared ReLU), the
routed and the shared expert's alike.  And
the width the routed experts act at: with `moe_latent` set,
`y = (Σ_k w_k · E_{i_k}(u W_down)) W_back + S(u)` — the router still
reads the full-width stream, the tiles gather, compute and scatter rows
of the latent's width, the sum is projected back once, and the shared
expert stays at full width.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs import metrics as obs_metrics
from ..ops import moe
from ..ops.attention import attention_reference, flash_attention

normal = nn.initializers.normal(0.02)
#: the name `gated_mlp` gives its first product's output
FFN_HIDDEN = "ffn_hidden"
#: the variable collection a layer's data-dependent counts ride out in
REPORTS = "reports"
#: what an expert layer's router may read (`router_input`)
ROUTER_INPUTS = ("ffn", "block")


def dense(features: int, name: str):
    return nn.Dense(features, use_bias=False, kernel_init=normal, name=name)


def gated_mlp(u, width: int, d_model: int, name: str = "mlp",
              form: str = "gated_silu"):
    """(silu(g) ⊙ v) W_out with [g, v] = u W_in — or, under another
    `form` of `ops.moe.EXPERT_FORMS`, that one: `<name>_in`,
    `<name>_out` in the calling module's scope.  The first product's
    output goes by a name: a block's recomputation keeps it in the
    layers whose policy lists the name (`models.hybrid`: a byte budget
    decides) and reads it there in place of a second `u W_in`; the
    activation is elementwise and is made again from it."""
    hidden = checkpoint_name(
        dense(moe.EXPERT_FORMS[form] * width, f"{name}_in")(u), FFN_HIDDEN)
    return dense(d_model, f"{name}_out")(moe.expert_hidden(hidden, form))


def causal_attention(q, k, v, attn_mode: str, scale: float, window=None):
    """Causal attention the way `attn_mode` says: `dense`, the plain
    form, or the flash kernels (`flash`; `flash_interpret` off the chip)
    — over the whole past or, with `window`, over the last `window` keys."""
    if attn_mode == "dense":
        return attention_reference(q, k, v, causal=True, scale=scale,
                                   window=window)
    if attn_mode in ("flash", "flash_interpret"):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               interpret=attn_mode == "flash_interpret",
                               window=window)
    raise ValueError(f"unknown attn_mode {attn_mode}")


class LatentAttention(nn.Module):
    cfg: Any         # models.hybrid.HybridConfig
    attn_mode: str   # dense | flash | flash_interpret

    @nn.compact
    def __call__(self, u):
        m = self.cfg
        B, T, _ = u.shape
        H, nope, rope, dv = m.num_heads, m.nope_dim, m.rope_dim, m.v_dim
        q = dense(H * (nope + rope), "q")(u).reshape(B, T, H, nope + rope)
        c, k_pe = jnp.split(dense(m.kv_rank + rope, "kv_a")(u),
                            [m.kv_rank], axis=-1)
        kv = dense(H * (nope + dv), "kv_b")(
            nn.RMSNorm(epsilon=m.eps, name="kv_norm")(c)
        ).reshape(B, T, H, nope + dv)
        # without positions nothing is turned: q is the product as it
        # stands, and no `rope` scope is in the program
        turns = m.mla_rope
        obs_metrics.model_mla_rope.set(int(turns))
        k_pe = k_pe[:, :, None, :]
        if turns:
            with jax.named_scope("rope"):
                q = jnp.concatenate(
                    [q[..., :nope], moe.rotary(q[..., nope:], m.rope_theta)],
                    axis=-1)
                k_pe = moe.rotary(k_pe, m.rope_theta)
        with jax.named_scope("rope" if turns else "mla_key"):
            # one shared head for all H: the copy the kernels' layout asks
            obs_metrics.latent_assembled_operands.set(1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))],
                axis=-1)
        # kept by name from a recomputation: making them again is the
        # rotary's pads and slices and the copy that assembles k — an
        # un-turned q is its product as it stands, and is made again
        if turns:
            q = checkpoint_name(q, "mla_q")
        k = checkpoint_name(k, "mla_k")
        v = kv[..., nope:]
        scale = 1.0 / math.sqrt(nope + rope)
        o = causal_attention(q, k, v, self.attn_mode, scale)
        return dense(m.d_model, "o")(o.reshape(B, T, H * dv))


class ExpertLayer(nn.Module):
    cfg: Any
    attn_mode: str = "dense"   # the model's: `flash` runs its kernels

    @nn.compact
    def __call__(self, u, plan=None, plan_only: bool = False):
        """The layer of u [B, T, d].  `plan_only`: u is what the router
        reads, and the call ends with the plan (`ops.moe.Dispatch`) — a
        router ahead of the mixer; `plan` given: u is what the experts
        read, routed as that plan says."""
        m = self.cfg
        B, T, d = u.shape
        first, held = m.experts_held
        if not 0 <= first < first + held <= m.experts:
            raise ValueError(f"experts_held {m.experts_held} is no range of "
                             f"the {m.experts} experts routed over")
        if not 0 < m.top_k <= m.experts:
            raise ValueError(f"top_k {m.top_k} of {m.experts} experts: a "
                             f"token's experts are distinct")
        width = m.moe_latent or d   # what the routed experts read and give
        wide = moe.EXPERT_FORMS[m.expert_form]
        obs_metrics.moe_experts.set(held, kind="held")
        obs_metrics.moe_experts.set(m.experts, kind="routed_over")
        obs_metrics.moe_top_k.set(m.top_k)
        obs_metrics.moe_latent_dim.set(m.moe_latent)
        obs_metrics.moe_shared_dim.set(m.shared_dim)
        obs_metrics.moe_dispatch_rows.set(
            moe.dispatch_rows(B * T, m.top_k, held))
        obs_metrics.moe_plan_sorted_operands.set(moe.PLAN_SORTED_OPERANDS)
        chunk = moe.add_rows_chunk(B * T, width, u.dtype, self.attn_mode)
        # a walk's two loops, forward and backward, each add a tile's rows
        obs_metrics.moe_add_rows.set(2 * bool(chunk), kind="kernel")
        obs_metrics.moe_add_rows.set(2 * (not chunk), kind="scatter")
        obs_metrics.moe_add_rows_chunk.set(chunk)
        for form in moe.ROUTER_FORMS:
            obs_metrics.moe_router_form.set(int(form == m.router_form),
                                            kind=form)
        for form in moe.EXPERT_FORMS:
            obs_metrics.moe_expert_form.set(int(form == m.expert_form),
                                            kind=form)
        for source in ROUTER_INPUTS:
            obs_metrics.moe_router_input.set(int(source == m.router_input),
                                             kind=source)
        if plan_only:
            return self._plan(u.reshape(B * T, d))
        # the shared expert first: with it after the routed path, XLA's
        # memory-space assignment moved another of its weights into VMEM
        # once the recomputation kept the router's results, and its
        # fusions ran 5.8 ms a step slower in `ns-train-backlog`
        # (PERF.md §6, PR 33: compile the whole fit and read which
        # operands of `shared` carry `S(1)` before moving this)
        shared = None   # `shared_dim` 0: the layer is its routed sum alone
        if m.shared_dim:
            with jax.named_scope("shared"):
                shared = gated_mlp(u, m.shared_dim, d, "shared",
                                   m.expert_form)
        x = u.reshape(B * T, d)
        if plan is None:
            plan = self._plan(x)
        self.sow(REPORTS, "expert_counts", plan.counts,
                 reduce_fn=lambda _, new: new, init_fn=lambda: 0)
        if m.moe_latent:
            with jax.named_scope("latent_proj"):
                x = dense(width, "latent_in")(x)
        routed = moe.experts_apply(
            x, plan,
            self.param("experts_in", normal,
                       (held, width, wide * m.expert_dim)),
            self.param("experts_out", normal, (held, m.expert_dim, width)),
            m.expert_form, self.attn_mode)
        if m.moe_latent:
            # `latent_out`'s weight gradient reads the routed sum: kept
            # by name, a recomputed forward does not walk the tiles for
            # it (at the stream's width nothing reads it, and XLA drops
            # the recomputed walk: PERF.md §6, PR 33)
            routed = checkpoint_name(routed, "routed_sum")
            with jax.named_scope("latent_proj"):
                routed = dense(d, "latent_out")(routed)
        routed = routed.reshape(B, T, d)
        return routed if shared is None else routed + shared

    def _plan(self, x):
        """x [N, d], what the router reads → the dispatch's plan."""
        m = self.cfg
        with jax.named_scope("router"):
            experts, weights = moe.route(
                x, self.param("router", normal, (x.shape[1], m.experts)),
                self.param("router_bias", normal, (m.experts,))
                if m.router_form == "sigmoid" else None,
                m.top_k, m.routed_scale, m.router_form)
            return moe.dispatch_plan(experts, weights, *m.experts_held,
                                     m.experts)


def record_reports(cfg, reports) -> None:
    """What a fit's reports say, into the registry: `reports` is the
    collection read back with the losses, every leaf
    [epochs, batches, experts] counts of one expert layer."""
    import numpy as np

    counts = jax.tree.leaves(reports)
    if not counts:
        return
    first, held = cfg.experts_held
    # [steps of every expert layer, experts]
    steps = np.concatenate([np.asarray(c, np.int64).reshape(-1, cfg.experts)
                            for c in counts])
    per_expert = steps.sum(axis=0)
    here = per_expert[first:first + held]
    # a step's live tiles (`ops.moe.dispatch_plan`): each expert held
    # takes whole tiles, so the rows walked are its assignments and the
    # padding that fills its last tile
    walked = moe.walked_rows(steps[:, first:first + held],
                             int(steps[0].sum()) // cfg.top_k)
    obs_metrics.moe_tile_rows.inc(float(here.sum()), kind="live")
    obs_metrics.moe_tile_rows.inc(float(walked.sum() - here.sum()),
                                  kind="padding")
    obs_metrics.moe_assignments.inc(float(here.sum()), kind="held")
    obs_metrics.moe_assignments.inc(
        float(per_expert.sum() - here.sum()), kind="elsewhere")
    obs_metrics.moe_expert_load.set(
        float(here.max() / max(here.mean(), 1e-30)))
