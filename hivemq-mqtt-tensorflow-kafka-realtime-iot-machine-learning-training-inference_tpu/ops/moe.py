"""Sparse-expert ops: rotary positions, the router, and the dropless
dispatch of tokens to the experts held here.

An expert layer routes every token over ALL `experts` of the model
(`ROUTER_FORMS`: sigmoid scores, a selection-only bias, the `top_k`
largest, weights renormalised over the selected and scaled — or the
`top_k` largest raw logits and a softmax over the selected) and
computes the part of the result that the experts it HOLDS give — a contiguous range
`[first, first + held)`, one chip's share under expert parallelism.
What the experts held elsewhere would add is left out; on one chip
there is no exchange.

Dropless under static shapes: no capacity and no token falls through.
The assignments are sorted by expert, each held expert's group laid out
from a tile's boundary (`dispatch_plan`), so every tile of `TILE` rows
belongs to one expert.  The layout is built for the worst the router
can produce — every token taking only experts held here — but only
LIVE tiles run: `experts_apply` walks them in a loop whose trip count
is data (gather a tile's tokens, the expert's MLP as two plain
products, scale by the routing weights, add onto the tokens), and its
backward walks them again.  Work and memory traffic follow the
assignments that landed here, a tile's padding at most an expert.

How a tile's rows are added back onto their tokens has two forms
(`add_rows_form`; nothing a configuration sets).
Where the model runs its kernels (`attn_mode` `flash`; interpreted
under `flash_interpret`), the rows are float32 of whole 128-lane tiles
and the `[N, d]` accumulator is larger than XLA keeps in VMEM (over
64 MiB: `st-train-backlog`'s 320 MiB, `lf-train-backlog`'s 128) the
Pallas call `iotml_add_rows` (`ops/add_rows.py`) copies a tile's live
rows of the accumulator into VMEM a row a DMA, adds, and copies them
back, and the loop carries the accumulator as `[N, 1, d]`, the layout
in which a row is one piece.  Otherwise — `dense`, the tiny presets'
and the CPU tests' narrow rows, and the small accumulators of
`km-train-backlog` (64 MiB) and `ns-train-backlog` (32) — XLA's
scatter-add: into an accumulator in HBM it takes 0.29 µs a 10 KB row
where its gather of the same rows takes 0.027 (two of them were 300 µs
of a 542 µs tile in `st-train-backlog`), into one it keeps in VMEM
0.08-0.09, and there the kernel's layout costs a loop more than the
kernel saves it: one change of layout behind a loop's last tile, 335 MB
at `st`'s shape (`km` ran 0.8% slower under the kernel, `ns` 0.6%
faster; PERF.md §6, PR 47).  Two cheaper forms of the scatter were
measured there and dropped — `indices_are_sorted=True`, and gather +
dense add + overwriting scatter: XLA's scatter stays a fusion that
takes a row at a time.  `add_rows.add_rows` is a `jax.jit` function,
for set-up's sake: the walks of every layer — forward, recomputed and
backward — trace the kernel once a shape and call one lowered function
(a bare `pallas_call` was traced at each of `st`'s twelve call sites,
3.65 s of its `setup_s`; PERF.md §6, PR 48).

An expert's form is data (`EXPERT_FORMS`): `gated_silu`,
`(silu(g) ⊙ v) W_out` with `[g, v] = x W_in` (`w_in` `[held, d, 2f]`),
`relu_gated`, the same with `relu(g)` for the gate, or `relu2`, the
non-gated `relu(x W_in)² W_out` (`[held, d, f]`).  The
rows may be narrower than the stream: a layer that computes its experts
in a latent hands the projected rows in and projects the sum back.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import add_rows


# --------------------------------------------------------------- rotary
def rotary(x, theta: float):
    """Rotary positions on the last axis of x [B, T, ..., R], positions
    0 … T−1 along axis 1: neighbouring features (x[2i], x[2i+1]) are one
    pair, turned by `t · theta^(−2i/R)`.  float32 angles.  (Pairs by a
    reshape; taking a pair's partner by rolls of the whole 192-wide head
    instead ran 36 ms a step slower in `km-train-backlog`: PERF.md §6,
    PR 30.)

    Who still calls it: `LatentAttention` (`km-train-backlog`: a 64-wide
    slice of a 192-wide head and ONE shared key head, assembled into the
    kernels' operands after the turn), and `GroupedAttention` under
    `dense` attention (the plain path the tests compare against) or
    where its heads fill no whole 128-lane tiles.  Where grouped
    attention turns the WHOLE head under the flash kernels
    (`lf-train-backlog`, `ou-train-backlog`) the same mathematics runs
    as the Pallas call `iotml_rope` on the projections' own
    `[B, T, H·D]` (`ops/rope.py`): XLA lays the `[…, R/2, 2]` array of
    this form out with T on the lanes and pays padded, transposing
    copies on both sides of the kernels to undo it (PERF.md §6,
    PR 43)."""
    T, R = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq     # [T, R/2]
    shape = (1, T) + (1,) * (x.ndim - 3) + (R // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    pairs = x.reshape(x.shape[:-1] + (R // 2, 2)).astype(jnp.float32)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------- router
def _mask(experts, n_experts: int):
    # the selection as [N, top_k, experts]: one masked sum takes the
    # selected entries out of [N, experts] or lays [N, top_k] out over
    # them (a gather or scatter of [N, top_k] scalars is the slower way
    # on a chip)
    return experts[..., None] == jnp.arange(n_experts)


#: a router's form: what a logit's score is and what the selected
#: scores' weights are — `sigmoid`: sigmoids, the selected over their
#: sum; `softmax_topk`: the raw logits, a softmax over the selected
#: (equal to a softmax over all, renormalised over the selected)
ROUTER_FORMS = ("sigmoid", "softmax_topk")


def _route_weights(picked, scale: float, form: str = "sigmoid"):
    if form == "softmax_topk":
        return jax.nn.softmax(picked, axis=-1) * scale
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scale


def _highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def route(u, gate, bias, top_k: int, scale: float, form: str = "sigmoid"):
    """u [N, d] → (experts [N, top_k] int32, weights [N, top_k] float32).

    Scores are sigmoids of a float32 product at `highest` (top-k is
    discontinuous: a rounded score picks another expert).  `bias` moves
    the SELECTION only — the weights are the selected scores without
    it, over their sum, times `scale` — and no gradient reaches it; a
    router without one hands None.  Under `softmax_topk` the scores are
    the product's own logits and the weights their softmax over the
    selected.

    The backward pass is written out, from the selection and the
    selected scores alone (`route_experts`, `route_picked` by name): a
    sigmoid's derivative at a selected entry is `picked · (1 − picked)`
    (a raw logit's is 1) and no other entry of [N, experts] has a
    gradient, so a caller that
    recomputes its forward (`jax.checkpoint` with a policy of names)
    runs the product, the sigmoid and top-k once.  The two products of
    the backward are float32 at `highest`, as the forward's is."""
    return _route_fwd(u, gate, bias, top_k, scale, form)[0]


def _route_fwd(u, gate, bias, top_k, scale, form="sigmoid"):
    if form not in ROUTER_FORMS:
        raise ValueError(f"unknown router form {form!r}: {ROUTER_FORMS}")
    s = _highest(u.astype(jnp.float32), gate.astype(jnp.float32))
    if form == "sigmoid":
        s = jax.nn.sigmoid(s)
    _, experts = jax.lax.top_k(s if bias is None else s + bias, top_k)
    experts = checkpoint_name(experts, "route_experts")
    picked = checkpoint_name(
        jnp.sum(jnp.where(_mask(experts, s.shape[-1]), s[:, None, :], 0.0),
                axis=-1), "route_picked")
    return (experts, _route_weights(picked, scale, form)), \
        (u, gate, bias, experts, picked)


def _route_bwd(top_k, scale, form, res, cotangents):
    u, gate, bias, experts, picked = res
    _, pull = jax.vjp(lambda p: _route_weights(p, scale, form), picked)
    (d_picked,) = pull(cotangents[1])
    if form == "sigmoid":
        d_picked = d_picked * picked * (1.0 - picked)
    d_logits = jnp.sum(jnp.where(
        _mask(experts, gate.shape[-1]), d_picked[..., None], 0.0), axis=1)
    return (_highest(d_logits, gate.astype(jnp.float32).T).astype(u.dtype),
            _highest(u.astype(jnp.float32).T, d_logits).astype(gate.dtype),
            None if bias is None else jnp.zeros_like(bias))


route.defvjp(_route_fwd, _route_bwd)


# ------------------------------------------------------------- dispatch
#: rows a tile of the dispatch holds: one expert's, so its two products
#: are plain ones.  At 512 rows a float32 tile's products do about as
#: many operations a byte of the expert's weights as a v5e's ridge asks.
TILE = 512


class Dispatch(NamedTuple):
    """The assignments sorted by expert, and the tiles that walk the
    groups of the experts held (`dispatch_plan`)."""
    token: jax.Array        # [A + tile] the sorted assignments' tokens
    weight: jax.Array       # [A + tile] their routing weights
    tile_expert: jax.Array  # [tiles] the expert held (local) of a tile
    tile_first: jax.Array   # [tiles] the sorted assignment it starts at
    tile_rows: jax.Array    # [tiles] its live rows (the rest is padding)
    live_tiles: jax.Array   # [] tiles that hold an assignment: the first
    counts: jax.Array       # [experts] assignments to every expert


def _tile(tokens: int) -> int:
    return min(TILE, -(-tokens // 8) * 8)


def dispatch_rows(tokens: int, top_k: int, held: int) -> int:
    """The static rows a layer's dispatch is built for: the worst the
    router can produce (a token's experts are distinct, so at most
    min(top_k, held) of them are held here) in whole tiles, and a tile
    of padding an expert."""
    tile = _tile(tokens)
    return -(-tokens * min(top_k, held) // tile) * tile + held * tile


def walked_rows(held_counts, tokens: int):
    """The rows of the live tiles a step of `tokens` walks, by expert
    held, of its assignments to them: every group in whole tiles."""
    tile = _tile(tokens)
    return -(-held_counts // tile) * tile


#: the name the plan's parts go by under a policy of names
_keep = functools.partial(checkpoint_name, name="dispatch_plan")
#: the operands the plan's sort carries: the key, the index that
#: becomes the order, and the routing weights
PLAN_SORTED_OPERANDS = 3


@jax.custom_vjp
def _sort_plan(key, weights):
    """The N·K assignments sorted by `key`, stably: (order [A] int32,
    the flat `weights` in that order).  The weights ride the sort as a
    third operand, so nothing gathers them by `order`; and `order` is a
    permutation, so their cotangents' way back is a second sort, by
    `order` itself, where autodiff's rule for a sort gathers by the
    permutation and transposes that into a scatter-add of scalars."""
    return _sort_plan_fwd(key, weights)[0]


def _sort_plan_fwd(key, weights):
    _, order, weight = jax.lax.sort(
        (key, jnp.arange(key.shape[0], dtype=jnp.int32), weights),
        num_keys=1, is_stable=True)
    order = _keep(order)
    return (order, _keep(weight)), order


def _sort_plan_bwd(order, cotangents):
    # every key is another: nothing for a stable sort to keep in order
    _, d_weights = jax.lax.sort((order, cotangents[1]), num_keys=1,
                                is_stable=False)
    return None, d_weights


_sort_plan.defvjp(_sort_plan_fwd, _sort_plan_bwd)


def dispatch_plan(experts, weights, first: int, held: int,
                  n_experts: int) -> Dispatch:
    """Sort the N·K assignments by expert (those held elsewhere last)
    and cut the groups of the experts held into tiles, each group from
    a tile of its own.  A group is one run of the sorted assignments, so
    a tile is a slice of them: nothing is gathered row by row, and the
    routing weights reach their sorted places inside the sort
    (`_sort_plan`).

    The sorted order and weights, the tiles' fields and `counts` go by
    one name, `dispatch_plan`: what a recomputed forward would sort and
    count a second time, and a policy of names keeps."""
    N, K = experts.shape
    tile = _tile(N)
    n_tiles = dispatch_rows(N, K, held) // tile
    flat = experts.reshape(-1)
    local = flat - first
    here = (local >= 0) & (local < held)
    order, weight = _sort_plan(jnp.where(here, local, held),
                               weights.reshape(-1))
    counts = _keep(jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype),
        axis=0, dtype=jnp.int32))
    sizes = jax.lax.dynamic_slice_in_dim(counts, first, held)
    tiles = -(-sizes // tile)                       # tiles a group takes
    tile_end = jnp.cumsum(tiles)
    c = jnp.arange(n_tiles)
    e = jnp.minimum(jnp.searchsorted(tile_end, c, side="right"), held - 1)
    within = (c - (tile_end - tiles)[e]) * tile     # rows of the group ahead
    pad = lambda v, fill: jnp.concatenate(  # noqa: E731
        [v, jnp.full((tile,), fill, v.dtype)])
    return Dispatch(
        pad(order // K, N), pad(weight, 0),
        _keep(e.astype(jnp.int32)),
        _keep(((jnp.cumsum(sizes) - sizes)[e] + within).astype(jnp.int32)),
        _keep(jnp.clip(sizes[e] - within, 0, tile).astype(jnp.int32)),
        _keep(tile_end[-1]), counts)


def plan_kept_bytes(tokens: int, top_k: int, held: int,
                    n_experts: int) -> int:
    """What a layer's router and plan keep by name, a step: the
    selection and the selected scores, the sorted order and the sorted
    weights, three fields a tile, the live tiles' count and `counts` —
    four bytes each."""
    tiles = dispatch_rows(tokens, top_k, held) // _tile(tokens)
    return 4 * (4 * tokens * top_k + 3 * tiles + 1 + n_experts)


#: an expert's form: the activation between its two products, and how
#: many times the expert's width `w_in` is wide
EXPERT_FORMS = {"gated_silu": 2, "relu2": 1, "relu_gated": 2}


def expert_hidden(h, form: str):
    """What an expert's second product reads, of its first's result h:
    `silu(g) ⊙ v` with `[g, v] = h`, `relu(g) ⊙ v` of the same, or
    `relu(h)²`."""
    if form in ("gated_silu", "relu_gated"):
        gate, value = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu if form == "gated_silu" else jax.nn.relu
        return act(gate) * value
    if form == "relu2":
        return jnp.square(jax.nn.relu(h))
    raise ValueError(f"unknown expert form {form!r}: {tuple(EXPERT_FORMS)}")


def _expert_tile(form, rows, w_in, w_out, weight):
    """One tile through its expert, times the rows' routing weights."""
    return (expert_hidden(rows @ w_in, form) @ w_out) * weight[:, None]


def _tile_operands(c, x, w_in, w_out, weight, plan: Dispatch):
    """Tile c: (its rows' tokens, its expert, which rows are live, how
    many — they come first —, the operands of `_expert_tile`).  A row of
    padding takes token N: zeros in, weight 0, nothing added back."""
    tile = _tile(x.shape[0])
    row = jnp.arange(tile)
    live_rows = plan.tile_rows[c]
    live = row < live_rows
    cut = lambda v: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        v, plan.tile_first[c], tile)
    tokens = jnp.where(live, cut(plan.token), x.shape[0])
    e = plan.tile_expert[c]
    return tokens, e, live, live_rows, (
        x.at[tokens].get(mode="fill", fill_value=0), w_in[e], w_out[e],
        jnp.where(live, cut(weight), 0).astype(x.dtype))


def add_rows_chunk(tokens: int, d: int, dtype, attn_mode: str) -> int:
    """The rows a grid step of `iotml_add_rows` adds back, for a layer
    of `tokens` tokens whose experts read and give rows `d` wide — or 0:
    XLA's scatter-add runs.  The kernel where the model runs its kernels
    (`attn_mode` `flash`, `flash_interpret`), the accumulator is too
    large for XLA to keep in VMEM (`add_rows.RESIDENT_BYTES`) and the
    tile suits the kernel (`add_rows.chunk_rows`: float32 rows of whole
    128-lane tiles); the scatter under `dense`, for a small accumulator
    and for narrow rows."""
    itemsize = jnp.dtype(dtype).itemsize
    if attn_mode == "dense" or tokens * d * itemsize <= add_rows.RESIDENT_BYTES:
        return 0
    return add_rows.chunk_rows(_tile(tokens), d, itemsize)


def add_rows_form(tokens: int, d: int, dtype, attn_mode: str) -> str:
    """How such a layer's tiles add their rows back (`_add_rows`):
    `scatter`, XLA's scatter-add, or the Pallas call `iotml_add_rows`,
    `kernel` or — interpreted — `kernel_interpret`."""
    if not add_rows_chunk(tokens, d, dtype, attn_mode):
        return "scatter"
    return "kernel" if attn_mode == "flash" else "kernel_interpret"


def _accumulator(x, add: str):
    """Zeros for the tiles' rows to be added onto: `[N, d]`, or for the
    kernel `[N, 1, d]`, whose layout on a TPU holds a row in one piece
    (`ops/add_rows.py`) — the loop carries it so, and the one change of
    layout a walk pays is behind its last tile."""
    N, d = x.shape
    return jnp.zeros((N, d) if add == "scatter" else (N, 1, d), x.dtype)


def _add_rows(acc, tokens, live_rows, rows, add: str):
    """`rows` onto `acc` at `tokens`, of which the first `live_rows`
    are live and distinct (a token meets an expert once) and the rest,
    a tile's padding, token N: dropped."""
    if add == "scatter":
        return acc.at[tokens].add(rows, mode="drop", unique_indices=True)
    return add_rows.add_rows(acc, tokens, live_rows, rows,
                             interpret=add == "kernel_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts(form, add, x, w_in, w_out, weight, plan: Dispatch):
    def tile_step(c, out):
        tokens, _, _, live_rows, operands = _tile_operands(
            c, x, w_in, w_out, weight, plan)
        return _add_rows(out, tokens, live_rows,
                         _expert_tile(form, *operands), add)

    return jax.lax.fori_loop(0, plan.live_tiles, tile_step,
                             _accumulator(x, add)).reshape(x.shape)


def _experts_fwd(form, add, x, w_in, w_out, weight, plan):
    return _experts(form, add, x, w_in, w_out, weight, plan), \
        (x, w_in, w_out, weight, plan)


def _experts_bwd(form, add, res, d_out):
    """The live tiles again: each recomputed and pulled back; an
    expert's weight gradients accumulate in place."""
    x, w_in, w_out, weight, plan = res

    def tile_step(c, grads):
        dx, d_in, d_outw, d_weight = grads
        tokens, e, live, live_rows, operands = _tile_operands(
            c, x, w_in, w_out, weight, plan)
        _, pull = jax.vjp(functools.partial(_expert_tile, form), *operands)
        d_rows, g_in, g_out, g_weight = pull(
            d_out.at[tokens].get(mode="fill", fill_value=0))
        at = plan.tile_first[c]
        # a tile's padding lies over the next group's first assignments
        kept = jax.lax.dynamic_slice_in_dim(d_weight, at, live.shape[0])
        return (_add_rows(dx, tokens, live_rows, d_rows, add),
                d_in.at[e].add(g_in), d_outw.at[e].add(g_out),
                jax.lax.dynamic_update_slice_in_dim(
                    d_weight, jnp.where(live, g_weight.astype(kept.dtype),
                                        kept), at, 0))

    dx, d_in, d_outw, d_weight = jax.lax.fori_loop(
        0, plan.live_tiles, tile_step,
        (_accumulator(x, add), jnp.zeros_like(w_in), jnp.zeros_like(w_out),
         jnp.zeros_like(weight)))
    return dx.reshape(x.shape), d_in, d_outw, d_weight, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def experts_apply(x, plan: Dispatch, w_in, w_out, form: str = "gated_silu",
                  attn_mode: str = "dense"):
    """Σ over the assignments held of `w · E_i(x)`, for x [N, d]:
    `E_i(x) = (silu(x W_gate,i) ⊙ x W_up,i) W_down,i` with
    `w_in[i] = [W_gate,i, W_up,i]` ([held, d, 2f]), or under `relu2`
    `relu(x W_up,i)² W_down,i` with `w_in` [held, d, f], under
    `relu_gated` the first with `relu` for `silu`; `w_out` [held, f,
    d].  Dropless: every assignment held has its row.  `attn_mode`, the
    model's, says whether its kernels run (`add_rows_form`)."""
    with jax.named_scope("experts"):
        return _experts(form, add_rows_form(*x.shape, x.dtype, attn_mode),
                        x, w_in, w_out, plan.weight, plan)


@functools.partial(jax.jit, static_argnames=("first", "held", "form"))
def experts_dense(x, experts, weights, w_in, w_out, first: int, held: int,
                  form: str = "gated_silu"):
    """The same sum with every expert held applied to every token and a
    weight that is zero where it was not selected: the form the tests
    hold the dispatch to (no sort, no grouped product)."""
    ids = first + jnp.arange(held)
    dense_w = jnp.sum(jnp.where(experts[..., None] == ids, weights[..., None],
                                0.0), axis=1)                    # [N, held]
    hidden = expert_hidden(jnp.einsum("nd,edf->enf", x, w_in), form)
    out = jnp.einsum("enf,efd->end", hidden, w_out)
    return jnp.einsum("end,ne->nd", out, dense_w.astype(out.dtype))
