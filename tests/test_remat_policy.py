"""What a block's recomputation keeps (`models.hybrid.KEPT`): the flash
kernel's `out` and log-sum-exp, the router's selection and plan, the
routed sum in a latent — by name, under `nn.remat`'s policy.

Three properties, each over the three shapes of stack the benchmark
trains (Mamba + grouped-query attention; latent attention + experts at
the stream's width; one-part layers + experts in a latent), at a tiny
preset on the CPU: the policy changes no gradient and no report; the
router's hand-written backward is autodiff of its forward; and in the
gradient's jaxpr the kernel and top-k appear once a layer, the `highest`
product three times and a sort twice (the plan's, and the one that
brings the routing weights' cotangents back) — with no gather and no
scatter-add of the routing weights' scalars."""

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
}
MODES = ("flash_interpret", "dense")


def _batch(B=2, T=40, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, T, 18)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, 1, 18)), jnp.float32),
            jnp.ones((B,), jnp.float32))


def _grads_and_reports(model, params, batch):
    loss = make_loss_fn(model, supervised=True)
    (_, aux), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params, *batch)
    return grads, aux[2:]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("stack", STACKS)
def test_the_policy_changes_no_gradient_and_no_report(monkeypatch, stack,
                                                      mode):
    """The kept values are the ones the recomputation would make: the
    loss's gradients and the expert layers' `reports` with the policy
    equal those of the same model under plain `nn.remat`."""
    model = SensorHybrid(STACKS[stack][0], attn_mode=mode)
    batch = _batch()
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]
    kept, kept_reports = _grads_and_reports(model, params, batch)

    plain = nn.remat
    monkeypatch.setattr(hybrid.nn, "remat", lambda cls, policy: plain(cls))
    again, again_reports = _grads_and_reports(model, params, batch)

    assert jax.tree.structure(kept) == jax.tree.structure(again)
    for got, want in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        scale = max(float(jnp.abs(want).max()), 1e-30)
        assert float(jnp.abs(got - want).max()) <= 2e-6 * scale
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), kept_reports, again_reports))
    assert bool(kept_reports) == bool(STACKS[stack][2])


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
            precision = eqn.params["precision"]
            return "highest" if precision is not None and all(
                p == jax.lax.Precision.HIGHEST for p in precision) else None
        if name in ("gather", "scatter-add"):
            aval = eqn.invars[0].aval
            return name if aval.shape == (assignments,) and jnp.issubdtype(
                aval.dtype, jnp.floating) else None
        return name if name in ("top_k", "sort") else None
    return found


@pytest.mark.parametrize("stack", STACKS)
def test_the_gradient_runs_kernel_top_k_and_sort_once_a_layer(monkeypatch,
                                                              stack):
    """In the gradient's jaxpr: `iotml_flash_fwd` once an attention
    layer (as often as the backward kernel), `top_k` once an expert
    layer, `sort` twice (the plan's, which carries the routing weights
    to their sorted places, and the one by the sorted order that brings
    their cotangents back), the `highest` product three times (the
    forward's and the backward's two), and the routing weights neither
    gathered nor scatter-added — and under plain `nn.remat`, the
    recomputed forward's top-k, sort and product beside them."""
    cfg, attention, routed = STACKS[stack]
    model = SensorHybrid(cfg, attn_mode="flash_interpret")
    batch = _batch()
    params = model.init(jax.random.PRNGKey(1), batch[0])["params"]
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

    plain = nn.remat
    monkeypatch.setattr(hybrid.nn, "remat", lambda cls, policy: plain(cls))
    again = counted()
    assert again.get("iotml_flash_fwd", 0) == 2 * attention
    assert of(again, "top_k", "sort", "highest") \
        == (2 * routed, 3 * routed, 4 * routed)
    assert of(again, "gather", "scatter-add") == (0, 0)
