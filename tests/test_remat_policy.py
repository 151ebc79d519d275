"""What a block's recomputation keeps (`models.hybrid.KEPT`): the flash
kernel's `out` and log-sum-exp, the router's selection and plan, the
routed sum in a latent — by name, under `nn.remat`'s policy — and, in
the layers a byte budget takes (`FFN_KEPT`, `kept_layers`), the
feed-forward part's first product.

Three properties, each over the five shapes of stack the benchmark
trains with recomputed blocks (Mamba + grouped-query attention; latent
attention + experts at the stream's width; one-part layers + experts in
a latent; gated short convolutions + experts without a shared one; a
looped stack of sandwich-normed layers, whose passes are a scan: what a
layer keeps it keeps in every pass, stacked), at
a tiny preset on the CPU, with the budget held to what keeps that product in
every layer that makes one, in the last of them and in none: the policy
changes no gradient and no report; the
router's hand-written backward is autodiff of its forward; and in the
gradient's jaxpr the kernel and top-k appear once a layer, the `highest`
product three times and a sort twice (the plan's, and the one that
brings the routing weights' cotangents back) — with no gather and no
scatter-add of the routing weights' scalars — and `mlp_in`'s or
`shared_in`'s product three times in a layer that keeps it, four times
in one that does not.  Then the counter beside the arrays the policy
saves, and the budget's rule itself, a pure function."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.models import hybrid
from iotml.models.hybrid import HybridConfig, SensorHybrid
from iotml.ops import moe
from iotml.train.loop import make_loss_fn

_EXPERTS = dict(experts=16, experts_held=(2, 4), top_k=3, expert_dim=24,
                shared_dim=48, routed_scale=2.5)
#: name → (configuration, attention layers, expert layers)
STACKS = {
    "mamba_gqa": (HybridConfig(), 1, 0),
    "mla_experts": (HybridConfig(
        layer_types=("mla", "mla", "mla"),
        ffn_types=("dense_ffn", "moe_ffn", "moe_ffn"), **_EXPERTS), 3, 2),
    "one_part_latent": (HybridConfig(
        layer_types=("mamba", "none", "attention", "none", "mamba"),
        ffn_types=("none", "moe_ffn", "none", "moe_ffn", "none"),
        num_heads=2, num_kv_heads=1, head_dim=16, moe_latent=32,
        expert_form="relu2", **dict(_EXPERTS, top_k=5)), 1, 2),
    "short_conv_no_share": (HybridConfig(
        layer_types=("short_conv", "attention", "short_conv"),
        ffn_types=("dense_ffn", "moe_ffn", "moe_ffn"), qk_norm=True,
        attn_rope_theta=10000.0, **dict(_EXPERTS, shared_dim=0)), 1, 2),
    "looped": (HybridConfig(
        layer_types=("attention", "attention"), num_kv_heads=4,
        attn_rope_theta=10000.0, loop_steps=3, post_norms=True), 2, 0),
}
MODES = ("flash_interpret", "dense")
#: the layers whose feed-forward product the budget is held to take
KEEPS = ("all", "last", "none")


def _batch(B=2, T=40, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, T, 18)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, 1, 18)), jnp.float32),
            jnp.ones((B,), jnp.float32))


def _hold_budget(monkeypatch, cfg, x, keep) -> tuple:
    """The byte budget set to what keeps the feed-forward part's first
    product as `keep` says; the layers that then keep it, and the
    layers that make one."""
    candidates = hybrid.ffn_hidden_bytes(cfg, x.shape[0] * x.shape[1],
                                         x.dtype.itemsize)
    makes = tuple(i for i, b in enumerate(candidates) if b)
    budget = {"all": sum(candidates), "none": 0,
              "last": candidates[makes[-1]]}[keep]
    monkeypatch.setattr(hybrid, "remat_budget", lambda *sizes: budget)
    keeps = hybrid.kept_layers(candidates, budget)
    assert keeps == {"all": makes, "last": makes[-1:], "none": ()}[keep]
    return keeps, makes


def _grads_and_reports(model, params, batch):
    loss = make_loss_fn(model, supervised=True)
    (_, aux), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params, *batch)
    return grads, aux[2:]


@pytest.mark.parametrize("keep", KEEPS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("stack", STACKS)
def test_the_policy_changes_no_gradient_and_no_report(monkeypatch, stack,
                                                      mode, keep):
    """The kept values are the ones the recomputation would make: the
    loss's gradients and the expert layers' `reports` with the policy
    equal those of the same model under plain `nn.remat`, whichever
    layers keep their feed-forward product."""
    model = SensorHybrid(STACKS[stack][0], attn_mode=mode)
    batch = _batch()
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]
    _hold_budget(monkeypatch, STACKS[stack][0], batch[0], keep)
    kept, kept_reports = _grads_and_reports(model, params, batch)

    plain = nn.remat
    monkeypatch.setattr(hybrid.nn, "remat", lambda target, policy=None: plain(target))
    again, again_reports = _grads_and_reports(model, params, batch)

    assert jax.tree.structure(kept) == jax.tree.structure(again)
    for got, want in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        scale = max(float(jnp.abs(want).max()), 1e-30)
        assert float(jnp.abs(got - want).max()) <= 2e-6 * scale
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), kept_reports, again_reports))
    # expert layers report, and a looped stack's objective
    assert bool(kept_reports) == bool(
        STACKS[stack][2] or STACKS[stack][0].loop_steps > 1)


def _route_by_autodiff(u, gate, bias, top_k, scale):
    """`ops.moe.route`'s forward as plain differentiable code."""
    s = jax.nn.sigmoid(jnp.dot(u, gate, precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return experts, weights * scale


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_routers_backward_is_autodiff_of_its_forward(seed):
    """Selection and weights as the plain forward gives them; the
    gradients to the stream and to the router's weights equal
    autodiff's through sigmoid, selection and normalisation; the bias
    moves the selection and gets no gradient."""
    rng = np.random.default_rng(seed)
    u, gate, bias, mix = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                          for shape in ((96, 32), (32, 16), (16,), (96, 5)))
    bias = 0.3 * bias

    def loss(route):
        return lambda u, gate, bias: jnp.sum(
            route(u, gate, bias, 5, 2.5)[1] * mix)

    assert all((a == b).all() if a.dtype == jnp.int32
               else jnp.allclose(a, b, rtol=1e-6) for a, b in zip(
                   moe.route(u, gate, bias, 5, 2.5),
                   _route_by_autodiff(u, gate, bias, 5, 2.5)))
    got = jax.grad(loss(moe.route), argnums=(0, 1, 2))(u, gate, bias)
    want = jax.grad(loss(_route_by_autodiff), argnums=(0, 1, 2))(
        u, gate, bias)
    for g, w in zip(got[:2], want[:2]):
        assert float(jnp.abs(g - w).max()) <= 2e-6 * float(jnp.abs(w).max())
    assert not got[2].any() and not want[2].any()
    # the bias did move the selection
    assert (moe.route(u, gate, bias, 5, 2.5)[0]
            != moe.route(u, gate, 0 * bias, 5, 2.5)[0]).any()


def _count(jaxpr, found, counts):
    """Equations of `jaxpr` and of every jaxpr inside it, by `found`."""
    for eqn in jaxpr.eqns:
        kind = found(eqn)
        if kind:
            counts[kind] = counts.get(kind, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, found, counts)
    return counts


def _what(assignments):
    """What `_count` counts of an equation; a `gather` or `scatter-add`
    only where its operand is a float vector of `assignments` entries:
    a layer's routing weights, or their cotangents, a scalar at a time."""
    def found(eqn):
        name = eqn.primitive.name
        if name == "pallas_call":
            return eqn.params["name"]
        if name == "dot_general":
            # a feed-forward part's first product, forward, recomputed
            # or backward, by the names its module and layer trace under
            where = str(eqn.source_info.name_stack)
            first = re.search(r"\b(?:mlp|shared)_in\b", where)
            if first:
                return "ffn_in:" + re.search(r"\blayer(\d+)\b", where)[1]
            precision = eqn.params["precision"]
            return "highest" if precision is not None and all(
                p == jax.lax.Precision.HIGHEST for p in precision) else None
        if name in ("gather", "scatter-add"):
            aval = eqn.invars[0].aval
            return name if aval.shape == (assignments,) and jnp.issubdtype(
                aval.dtype, jnp.floating) else None
        return name if name in ("top_k", "sort") else None
    return found


@pytest.mark.parametrize("keep", KEEPS)
@pytest.mark.parametrize("stack", STACKS)
def test_the_gradient_runs_kernel_top_k_and_sort_once_a_layer(monkeypatch,
                                                              stack, keep):
    """In the gradient's jaxpr: `iotml_flash_fwd` once an attention
    layer (as often as the backward kernel), `top_k` once an expert
    layer, `sort` twice (the plan's, which carries the routing weights
    to their sorted places, and the one by the sorted order that brings
    their cotangents back), the `highest` product three times (the
    forward's and the backward's two), and the routing weights neither
    gathered nor scatter-added; `mlp_in`'s or `shared_in`'s product
    three times in a layer that keeps its output (forward and the
    backward's two) and four times in one that makes it again — and
    under plain `nn.remat`, the recomputed forward's top-k, sort and
    products beside them."""
    cfg, attention, routed = STACKS[stack]
    model = SensorHybrid(cfg, attn_mode="flash_interpret")
    batch = _batch()
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]
    keeps, makes = _hold_budget(monkeypatch, cfg, batch[0], keep)
    loss = make_loss_fn(model, supervised=True)
    what = _what(batch[0].shape[0] * batch[0].shape[1] * cfg.top_k)

    def counted():
        jax.clear_caches()
        return _count(jax.make_jaxpr(jax.grad(loss, has_aux=True))(
            params, *batch).jaxpr, what, {})

    def of(counts, *names):
        return tuple(counts.get(name, 0) for name in names)

    kept = counted()
    assert kept.get("iotml_flash_fwd", 0) == attention \
        == kept.get("iotml_flash_bwd_dkv", 0)
    assert of(kept, "top_k", "sort", "highest") \
        == (routed, 2 * routed, 3 * routed)
    assert of(kept, "gather", "scatter-add") == (0, 0)
    assert {k: n for k, n in kept.items() if k.startswith("ffn_in:")} \
        == {f"ffn_in:{i}": 3 if i in keeps else 4 for i in makes}

    plain = nn.remat
    monkeypatch.setattr(hybrid.nn, "remat", lambda target, policy=None: plain(target))
    again = counted()
    assert again.get("iotml_flash_fwd", 0) == 2 * attention
    assert of(again, "top_k", "sort", "highest") \
        == (2 * routed, 3 * routed, 4 * routed)
    assert of(again, "gather", "scatter-add") == (0, 0)
    assert of(again, *(f"ffn_in:{i}" for i in makes)) == (4,) * len(makes)


def _through(jaxpr) -> dict:
    """jax hands a saved residual on through a `reduce_precision`."""
    return {id(eqn.invars[0]): id(eqn.outvars[0]) for eqn in jaxpr.eqns
            if eqn.primitive.name == "reduce_precision"}


def _read_back(jaxpr) -> set:
    """The values a recomputation in `jaxpr` reads."""
    return {id(v) for eqn in jaxpr.eqns
            if eqn.primitive.name in ("remat2", "checkpoint")
            for v in eqn.invars}


def _named(jaxpr, name):
    """(the value, or what a `reduce_precision` made of it) of every
    value named `name` in `jaxpr`."""
    through = _through(jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name" and eqn.params["name"] == name:
            out = id(eqn.outvars[0])
            yield eqn.outvars[0], through.get(out, out)


def _saved(jaxpr, name, found):
    """The avals of the values named `name` that a recomputation in
    `jaxpr` reads back from the forward pass: the ones the policy
    saved.  Where the passes of a loop are a scan, the forward scan
    stacks what its body named and the backward scan's body reads a
    pass's slice of it back: the stacked array is what was saved."""
    read = _read_back(jaxpr)
    for value, out in _named(jaxpr, name):
        if out in read:
            found.append(value.aval)
    scans = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "scan"]
    sliced = set()   # stacked arrays a backward body's recomputation reads
    for eqn in scans:
        body, first = eqn.params["jaxpr"].jaxpr, \
            eqn.params["num_consts"] + eqn.params["num_carry"]
        read = _read_back(body)
        sliced |= {id(outer) for outer, inner in zip(
            eqn.invars[first:], body.invars[first:]) if id(inner) in read}
    for eqn in scans:
        body = eqn.params["jaxpr"].jaxpr
        place = {id(v): i for i, v in enumerate(body.outvars)}
        for _, out in _named(body, name):
            stacked = eqn.outvars[place[out]] if out in place else None
            if stacked is not None and id(stacked) in sliced:
                found.append(stacked.aval)
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _saved(sub, name, found)
    return found


@pytest.mark.parametrize("keep", KEEPS)
@pytest.mark.parametrize("stack", STACKS)
def test_the_counter_says_the_bytes_the_policy_saves(monkeypatch, stack,
                                                     keep):
    """`iotml_remat_kept_bytes{kind="ffn"}` is the bytes of the arrays
    named `FFN_KEPT` that the gradient's recomputations read back from
    the forward pass, `iotml_remat_kept_layers` their count, and
    `iotml_remat_keepable_layers` the layers that make one."""
    from iotml.obs.metrics import default_registry

    cfg = STACKS[stack][0]
    model = SensorHybrid(cfg, attn_mode="flash_interpret")
    batch = _batch()
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]
    keeps, makes = _hold_budget(monkeypatch, cfg, batch[0], keep)
    jax.clear_caches()
    saved = _saved(jax.make_jaxpr(jax.grad(
        make_loss_fn(model, supervised=True), has_aux=True))(
            params, *batch).jaxpr, hybrid.FFN_KEPT, [])
    said = default_registry.collect()
    assert said['iotml_remat_kept_bytes{kind="ffn"}'] \
        == sum(a.size * a.dtype.itemsize for a in saved)
    assert said['iotml_remat_kept_layers{kind="ffn"}'] == len(saved) \
        == len(keeps)
    assert said['iotml_remat_keepable_layers{kind="ffn"}'] == len(makes)
    assert bool(saved) == (keep != "none")
    if cfg.loop_steps > 1:
        # every pass's, stacked: the kernel's out and lse a layer, and
        # the stream-sized inputs the scan keeps beside the names
        jaxpr = jax.make_jaxpr(jax.grad(
            make_loss_fn(model, supervised=True), has_aux=True))(
                params, *batch).jaxpr
        flash = _saved(jaxpr, "flash_out", []) + _saved(jaxpr, "flash_lse", [])
        assert all(a.shape[0] == cfg.loop_steps for a in saved + flash)
        assert said['iotml_remat_kept_bytes{kind="flash"}'] \
            == sum(a.size * a.dtype.itemsize for a in flash)
        forward = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
        stream = (cfg.loop_steps,) + batch[0].shape[:2] + (cfg.d_model,)
        assert said['iotml_remat_kept_bytes{kind="loop_inputs"}'] == sum(
            v.aval.size * v.aval.dtype.itemsize for v in forward.outvars
            if v.aval.shape == stream)
    else:
        assert said['iotml_remat_kept_bytes{kind="loop_inputs"}'] == 0


@pytest.mark.parametrize("candidates, budget, kept", [
    ((40, 40, 40), 39, ()),               # nothing fits: no layer
    ((40, 40, 40), 80, (1, 2)),           # the last two, in the stack's order
    ((40, 40, 40), 119, (1, 2)),          # between two sums: still two
    ((40, 40, 40), 120, (0, 1, 2)),       # all fit: all
    ((90, 20, 20), 100, (1, 2)),          # stops at the first that does not
    ((0, 40, 0, 40, 0), 80, (1, 3)),      # a layer without one is no candidate
    ((0, 40, 0, 40, 0), 79, (3,)),
    ((), 10, ()), ((0, 0), 10, ()), ((40,), 0, ()),
])
def test_the_budget_takes_the_last_layers_that_fit(candidates, budget, kept):
    assert hybrid.kept_layers(candidates, budget) == kept


@pytest.mark.parametrize("overrides, tokens, want", [
    ({}, 80, (80 * 256 * 4,) * 3),        # gated: [g, v] of mlp_dim each
    (dict(ffn_types=("dense_ffn", "none", "moe_ffn"), shared_dim=48), 10,
     (10 * 256 * 4, 0, 10 * 96 * 4)),     # `none`: no candidate
    (dict(ffn_types=("moe_ffn",) * 3, shared_dim=0), 80, (0, 0, 0)),
    (dict(ffn_types=("moe_ffn",) * 3, shared_dim=48, expert_form="relu2"),
     80, (80 * 48 * 4,) * 3),             # non-gated: one product's width
    (dict(loop_steps=4), 80, (4 * 80 * 256 * 4,) * 3),   # in every pass
])
def test_a_layer_without_a_first_product_is_no_candidate(overrides, tokens,
                                                         want):
    cfg = HybridConfig(**overrides)
    assert hybrid.ffn_hidden_bytes(cfg, tokens, 4) == want
    assert hybrid.kept_layers(want, sum(want)) \
        == tuple(i for i, b in enumerate(want) if b)


@pytest.mark.parametrize("limit, held, kept, budget", [
    (1000, 400, 300, 100),    # a third of what the arrays and the names leave
    (1000, 1200, 0, 0),       # arrays past the device's memory: nothing
    (hybrid.DEVICE_BYTES, 2 ** 30, 0, 5 * 2 ** 30),
    # a looped stack at the sixth cell's size: five times its 1.64 GB of
    # parameters (the gradients live through the backward pass) and what
    # 32 applications keep leave 1.37 GB, under a layer's four 369 MB
    (16_909_336_064, 5 * 1_644_748_876, 2_164_260_864 + 2_415_919_104,
     1_368_470_572),
])
def test_the_budget_is_a_third_of_what_the_arrays_leave(limit, held, kept,
                                                        budget):
    assert hybrid.remat_budget(limit, held, kept) == budget
    # this backend reports no `bytes_limit`: the constant stands in
    assert hybrid.device_bytes() == hybrid.DEVICE_BYTES
