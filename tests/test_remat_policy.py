"""What a block's recomputation keeps (`models.hybrid.KEPT`): the flash
kernel's `out` and log-sum-exp, the router's selection and plan, the
routed sum in a latent — by name, under `nn.remat`'s policy — and, in
the layers a byte budget takes (`BUDGETED`, `budget_takes`), the
feed-forward part's first product and, in a sandwich block, the parts'
outputs ahead of their post norms.

Three properties, each over the shapes of stack the catalogue states
(`stacks.STACKS`: the benchmark's seven hybrid configurations at their
tiny presets — Mamba + grouped-query attention; latent attention +
experts at the stream's width; one-part layers + experts in a latent;
gated short convolutions + experts without a shared one; global and
sliding-window attention + experts routed on the block's input, which
makes no value the budget could buy; a looped stack
of sandwich-normed layers, whose passes are a scan: what a layer keeps
it keeps in every pass, stacked — and a sandwich stack that is no loop),
on the CPU, with the budget held to what keeps every candidate, what
keeps the first product in the last layer that makes one (and what is
dearer than it: a sandwich block's feed-forward output, in every layer)
and nothing, each program made once a worker (`stacks.policy_run`): the
policy changes no gradient and no report; the router's hand-written
backward is autodiff of its forward; and in the
gradient's jaxpr the kernel and top-k appear once a layer, the `highest`
product three times and a sort twice (the plan's, and the one that
brings the routing weights' cotangents back) — with no gather and no
scatter-add of the routing weights' scalars — and `mlp_in`'s or
`shared_in`'s product three times in a layer that keeps it, four times
in one that does not, and a sandwich block's `mlp_out` likewise by its
output's name.  Then the counter beside the arrays the policy
saves; a pin of what the module listed and said before its kept values
were rows of one table; and the budget's rule itself, pure functions:
the order, the candidates of a configuration, and what the rule takes
at the shapes of the benchmark's five hybrid cells."""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import Candidate, HybridConfig, SensorHybrid
from iotml.ops import moe
from stacks import KEEPS, MODES, PLAIN, STACKS, nbytes, policy_run


@pytest.fixture(scope="module", params=[(stack, keep) for stack in STACKS
                                        for keep in KEEPS],
                ids=lambda held: "-".join(held))
def held(request):
    """(a shape of stack, what the byte budget is held to keep): of
    module scope, so that the cases of one pair are neighbours whichever
    test they are of, and a worker that is handed them makes each
    program (`stacks.policy_run`) once."""
    return request.param


@pytest.mark.parametrize("mode", MODES)
def test_the_policy_changes_no_gradient_and_no_report(held, mode):
    """The kept values are the ones the recomputation would make: the
    loss's gradients and the expert layers' `reports` with the policy
    equal those of the same model under plain `nn.remat`, whichever
    layers keep their feed-forward product."""
    stack, keep = held
    kept, again = policy_run(stack, mode, keep), policy_run(stack, mode, PLAIN)
    assert jax.tree.structure(kept.grads) == jax.tree.structure(again.grads)
    for got, want in zip(jax.tree.leaves(kept.grads),
                         jax.tree.leaves(again.grads)):
        scale = max(float(jnp.abs(want).max()), 1e-30)
        assert float(jnp.abs(got - want).max()) <= 2e-6 * scale
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), kept.reports, again.reports))
    # expert layers report, and a looped stack's objective
    assert bool(kept.reports) == bool(
        STACKS[stack].routed or stacks.config(stack).loop_steps > 1)


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


def test_the_gradient_runs_kernel_top_k_and_sort_once_a_layer(held):
    """In the gradient's jaxpr: `iotml_flash_fwd` once an attention
    layer (as often as the backward kernel), `top_k` once an expert
    layer, `sort` twice (the plan's, which carries the routing weights
    to their sorted places, and the one by the sorted order that brings
    their cotangents back), the `highest` product three times (the
    forward's and the backward's two), and the routing weights neither
    gathered nor scatter-added; `mlp_in`'s or `shared_in`'s product
    three times in a layer that keeps its output (forward and the
    backward's two) and four times in one that makes it again; in a
    sandwich block `mlp_out`'s product likewise, three times where the
    policy keeps the post norm's input and four where the norm's
    backward has it made again (in a block without post norms nothing
    reads it in the backward: three) — and
    under plain `nn.remat`, the recomputed forward's top-k, sort and
    products beside them."""
    stack, keep = held
    cfg, attention, routed = stacks.config(stack), \
        STACKS[stack].attention, STACKS[stack].routed
    run = policy_run(stack, "flash_interpret", keep)

    def of(counts, *names):
        return tuple(counts.get(name, 0) for name in names)

    kept = run.counts
    assert kept.get("iotml_flash_fwd", 0) == attention \
        == kept.get("iotml_flash_bwd_fused", 0)
    assert "iotml_flash_bwd_dkv" not in kept
    assert of(kept, "top_k", "sort", "highest") \
        == (routed, 2 * routed, 3 * routed)
    assert of(kept, "gather", "scatter-add") == (0, 0)
    assert {k: n for k, n in kept.items() if k.startswith("ffn_in:")} \
        == {f"ffn_in:{i}": 3 if i in run.keeps else 4 for i in run.makes}
    dense = [i for i, ffn in enumerate(cfg.ffn_kinds()) if ffn == "dense_ffn"]
    out_kept = {c.layer for c in run.taken if c.name == hybrid.FFN_OUT}
    assert {k: n for k, n in kept.items() if k.startswith("ffn_out:")} \
        == {f"ffn_out:{i}": 3 + (cfg.post_norms and i not in out_kept)
            for i in dense}

    again = policy_run(stack, "flash_interpret", PLAIN).counts
    assert again.get("iotml_flash_fwd", 0) == 2 * attention
    assert of(again, "top_k", "sort", "highest") \
        == (2 * routed, 3 * routed, 4 * routed)
    assert of(again, "gather", "scatter-add") == (0, 0)
    assert of(again, *(f"ffn_in:{i}" for i in run.makes)) \
        == (4,) * len(run.makes)
    assert of(again, *(f"ffn_out:{i}" for i in dense)) \
        == (3 + cfg.post_norms,) * len(dense)


def test_the_counter_says_the_bytes_the_policy_saves(held):
    """`iotml_remat_kept_bytes{kind=…}` of each budgeted name's kind
    (`ffn`: `FFN_KEPT`; `ffn_out`, `mixer_out`: a sandwich block's
    parts' outputs) is the bytes of the arrays of that name that the
    gradient's recomputations read back from the forward pass,
    `iotml_remat_kept_layers` their count, and
    `iotml_remat_keepable_layers` the layers that make one."""
    stack, keep = held
    cfg = stacks.config(stack)
    run = policy_run(stack, "flash_interpret", keep)
    said = run.said
    sandwiched = len(cfg.layer_types) * cfg.post_norms
    saved = []
    for name, kind in hybrid.BUDGETED.items():
        found = run.saved[name]
        assert said[f'iotml_remat_kept_bytes{{kind="{kind}"}}'] \
            == nbytes(found)
        assert said[f'iotml_remat_kept_layers{{kind="{kind}"}}'] \
            == len(found) == sum(c.name == name for c in run.taken)
        assert said[f'iotml_remat_keepable_layers{{kind="{kind}"}}'] \
            == (len(run.makes) if name == hybrid.FFN_KEPT else sandwiched)
        saved += found
    # (a stack without a first product and without post norms has
    # nothing the budget could buy, whatever it is held to)
    assert bool(saved) == (keep != "none" and bool(
        run.makes or cfg.post_norms))
    if cfg.loop_steps > 1:
        # every pass's, stacked: the kernel's out and lse a layer, and
        # the stream-sized inputs the scan keeps beside the names (a
        # kept part's output is of the stream's size too)
        flash = [*run.saved["flash_out"], *run.saved["flash_lse"]]
        assert all(a.shape[0] == cfg.loop_steps for a in saved + flash)
        assert said['iotml_remat_kept_bytes{kind="flash"}'] == nbytes(flash)
        assert sum(said[f'iotml_remat_kept_bytes{{kind="{kind}"}}']
                   for kind in ("loop_inputs", "ffn_out", "mixer_out")) \
            == run.stacked_streams
    else:
        assert said['iotml_remat_kept_bytes{kind="loop_inputs"}'] == 0


#: what every layer's policy lists, and at every row's tiny preset: the
#: names the budget buys a layer, in the order it buys them; kind →
#: (bytes, layers kept, layers that make one) of every kind that reads
#: other than 0 under `dense`; `flash`'s bytes under the kernels
_ALWAYS = ("flash_out", "flash_lse", "mla_q", "mla_k", "route_experts",
           "route_picked", "dispatch_plan", "routed_sum")
_ALL = ("ffn_out", "ffn_hidden", "mixer_out")
_SAID = {
    "granite": ((("ffn_hidden",),) * 3, {"ffn": (129024, 3, 3)}, 11424),
    "kimi": ((("ffn_hidden",),) * 3,
             {"ffn": (92160, 3, 3), "latent_qk": (184320, 0, 0),
              "router": (7984, 0, 0)}, 65280),
    "nemotron": (((), ("ffn_hidden",), (), ("ffn_hidden",), ()),
                 {"ffn": (30720, 2, 2), "experts": (20480, 0, 0),
                  "router": (13128, 0, 0)}, 10880),
    "lfm2": ((("ffn_hidden",), (), (), (), ()),
             {"ffn": (61440, 1, 1), "router": (15968, 0, 0)}, 21760),
    # nothing the budget could buy: experts without a shared one in every
    # layer, no post norms
    "smallthinker": (((),) * 4, {"router": (15968, 0, 0)}, 87040),
    # the dense MLP's product and four shared experts'; one latent layer
    # WITHOUT positions keeps its assembled k and not its un-turned q
    "kimi_linear": ((("ffn_hidden",),) * 5,
                    {"ffn": (122880, 5, 5), "latent_qk": (30720, 0, 0),
                     "router": (15968, 0, 0)}, 21760),
    "ouro": ((_ALL,) * 2,
             {"ffn": (491520, 2, 2), "ffn_out": (163840, 2, 2),
              "mixer_out": (163840, 2, 2), "loop_inputs": (245760, 0, 0)},
             174080),
    "sandwich": ((_ALL,) * 2,
                 {"ffn": (163840, 2, 2), "ffn_out": (40960, 2, 2),
                  "mixer_out": (40960, 2, 2)}, 21760),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("stack", STACKS)
def test_what_every_layer_lists_and_every_gauge_says(stack, mode):
    """The names each layer's policy lists and every `iotml_remat_*`
    series, at the row's tiny preset with the budget as the device gives
    it."""
    bought, kinds, flash = _SAID[stack]
    model = SensorHybrid(stacks.config(stack), attn_mode=mode)
    jax.clear_caches()
    listed = stacks.policy_names(
        model, stacks.batch(T=STACKS[stack].window)[0])
    assert listed == {f"layer{i}": _ALWAYS + names
                      for i, names in enumerate(bought)}
    want = {"iotml_remat_blocks": len(bought)}
    if mode != "dense":
        kinds = dict(kinds, flash=(flash, 0, 0))
    for kind, values in kinds.items():
        want.update({f'iotml_remat_{what}{{kind="{kind}"}}': value
                     for what, value in zip(stacks.REMAT_GAUGES, values)
                     if value})
    said = stacks.remat_gauges(stacks.default_registry.collect())
    # a kind's bytes; a budgeted kind's layers, kept and keepable; blocks
    assert len(said) == len(hybrid.TABLE) + 2 * len(hybrid.BUDGETED) + 1
    assert said == {**dict.fromkeys(said, 0), **want}


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


def _c(spec: str) -> tuple:
    """`"a:40@1 a:40@1 b:30@2"` → candidates: a name, its bytes and its
    density, the layers counted a name in the order written."""
    seen, out = {}, []
    for word in spec.split():
        name, rest = word.split(":")
        size, density = rest.split("@")
        layer = seen[name] = seen.get(name, -1) + 1
        out.append(Candidate(name, layer, int(size), float(density)))
    return tuple(out)


@pytest.mark.parametrize("spec, budget, taken", [
    # the dearest first, then the last layer of the cheaper name
    ("a:40@1 a:40@1 b:30@2 b:30@2", 100, "b0 b1 a1"),
    ("a:40@1 a:40@1 b:30@2 b:30@2", 59, "b1"),
    # equals: in the order listed, each from its last layer down
    ("a:40@1 a:40@1 b:30@1 b:30@1", 70, "a1 b1"),
    ("b:30@1 b:30@1 a:40@1 a:40@1", 70, "b0 b1"),
    # a name that does not fit does not stop a cheaper one behind it
    ("a:90@2 a:90@2 b:20@1 b:20@1", 50, "b0 b1"),
    ("a:90@1 b:20@1 b:20@1", 50, "b0 b1"),           # nor an equal one
    # within a name it stops at the first that does not fit
    ("a:20@1 a:90@1 b:30@1", 50, "b0"),
    # one name, one density: today's rule (`kept_layers`)
    ("a:40@1 a:40@1 a:40@1", 80, "a1 a2"),
    ("a:40@1 a:0@1 a:40@1 a:0@1", 79, "a2"),         # 0 bytes: no candidate
    # a name of two densities (two kinds of mixer): the dearer layers first
    ("m:40@1 m:40@3 m:40@1 m:40@3", 120, "m1 m3 m2"),
    ("", 10, ""), ("a:40@1", 0, ""),
])
def test_the_budget_buys_the_dearest_to_remake_a_byte_first(spec, budget,
                                                           taken):
    got = hybrid.budget_takes(_c(spec), budget)
    assert " ".join(f"{c.name}{c.layer}" for c in got) == taken
    assert sum(c.bytes for c in got) <= budget


_STREAM = 80 * 64 * 4   # a [tokens, d_model] value of the default widths


@pytest.mark.parametrize("overrides, want", [
    # no post norms: the first products alone, a product over d_model 64
    ({}, [("ffn_hidden", i, 80 * 256 * 4, 32.0) for i in range(3)]),
    # a sandwich block: its parts' outputs beside them — the MLP's a
    # product over mlp_dim 128, a mixer's over its heads' features
    (dict(post_norms=True),
     [("ffn_hidden", i, 80 * 256 * 4, 32.0) for i in range(3)]
     + [("ffn_out", i, _STREAM, 64.0) for i in range(3)]
     + [("mixer_out", i, _STREAM, 32.0) for i in range(3)]),
    # in every pass of a loop; heads of a stated width
    (dict(post_norms=True, loop_steps=4, layer_types=("attention",),
          head_dim=48),
     [("ffn_hidden", 0, 4 * 80 * 256 * 4, 32.0),
      ("ffn_out", 0, 4 * _STREAM, 64.0),
      ("mixer_out", 0, 4 * _STREAM, 96.0)]),
    # a loop without post norms has no more candidates than a stack
    (dict(loop_steps=4, layer_types=("attention",)),
     [("ffn_hidden", 0, 4 * 80 * 256 * 4, 32.0)]),
    # one-part layers: the part a layer lacks is no candidate (0 bytes);
    # an expert layer's output contracts over its shared width and a
    # token's routed ones, latent attention's over its value heads
    (dict(post_norms=True, layer_types=("mla", "none", "short_conv"),
          ffn_types=("none", "moe_ffn", "dense_ffn")),
     [("ffn_hidden", 0, 0, 32.0), ("ffn_hidden", 1, 80 * 64 * 4, 32.0),
      ("ffn_hidden", 2, 80 * 256 * 4, 32.0),
      ("ffn_out", 0, 0, 0.0), ("ffn_out", 1, _STREAM, (32 + 2 * 32) / 2),
      ("ffn_out", 2, _STREAM, 64.0),
      ("mixer_out", 0, _STREAM, 4 * 16 / 2), ("mixer_out", 1, 0, 0.0),
      ("mixer_out", 2, _STREAM, 32.0)]),
])
def test_a_sandwich_blocks_outputs_are_candidates(overrides, want):
    """A candidate's bytes are a step's over all passes, its density
    twice the width its product contracts over by an element's bytes."""
    cfg = HybridConfig(**overrides)
    assert list(hybrid.budget_candidates(cfg, 80, 4)) \
        == [Candidate(*c) for c in want]


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


@pytest.mark.parametrize("limit, held, kept, backward, budget", [
    (1000, 400, 300, 0, 100),  # a third of what the arrays and the names leave
    (1000, 400, 200, 100, 100),       # and the widest mixer's backward
    (1000, 1200, 0, 0, 0),     # arrays past the device's memory: nothing
    (hybrid.DEVICE_BYTES, 2 ** 30, 0, 0, 5 * 2 ** 30),
    # a looped stack at the sixth cell's size: five times its 1.64 GB of
    # parameters (the gradients live through the backward pass) and what
    # 32 applications keep leave 1.37 GB, under a layer's four 369 MB
    (16_909_336_064, 5 * 1_644_748_876, 2_164_260_864 + 2_415_919_104, 0,
     1_368_470_572),
])
def test_the_budget_is_a_third_of_what_the_arrays_leave(limit, held, kept,
                                                        backward, budget):
    assert hybrid.remat_budget(limit, held, kept, backward) == budget
    # this backend reports no `bytes_limit`: the constant stands in
    assert hybrid.device_bytes() == hybrid.DEVICE_BYTES


_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
#: the chip's `bytes_limit` (a TPU v5e's, as the benchmark's runs read it)
_CHIP_BYTES = 16_909_336_064
_NO_OUTS = {"ffn_out": (), "mixer_out": ()}


@pytest.mark.parametrize("stem, first_product, outs", [
    # without post norms: the first product alone, where it was (PR 39)
    ("granite-4.0-h-micro", (4, 5, 6, 7, 8, 9), _NO_OUTS),
    ("kimi-vl-a3b-instruct", (0, 1, 2, 3, 4, 5), _NO_OUTS),
    ("nemotron-3-super-120b-a12b", (1, 3, 5, 8, 10), _NO_OUTS),
    ("lfm2-24b-a2b", (0,), _NO_OUTS),
    # experts without a shared one in every layer: no first product
    ("smallthinker-21b-a3b", (), _NO_OUTS),
    # one window a step: a delta-rule mixer's backward holds 3.22 GB at
    # once, so the budget is 1.62 GB — four shared experts' 134 MB each,
    # and not the dense MLP's 1.208 GB behind them
    ("kimi-linear-48b-a3b", (1, 2, 3, 4), _NO_OUTS),
    # a sandwich block under a loop: the MLP's output in all six layers;
    # no room then for a first product of 1.476 GB in the 0.80 GB left
    ("ouro-2.6b", (), {"ffn_out": (0, 1, 2, 3, 4, 5), "mixer_out": (4, 5)}),
])
def test_what_the_rule_takes_at_the_benchmarks_shapes(stem, first_product,
                                                      outs):
    """By arithmetic alone (shapes, no array): the seven hybrid
    configurations at their jobs' sizes on the chip's memory — the six
    without post norms keep what `kept_layers` alone gave them, and
    `ou` buys its feed-forward outputs first."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", stem),
        os.path.join(_CONFIGS, f"sensorformer-{stem}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(_CONFIGS, f"sensorformer-{stem}.json")) as fh:
        cfg = json.load(fh)
    job, m = cfg["job"], mod.hybrid_config(cfg)
    model = SensorHybrid(m, features=cfg["model"]["features"],
                         attn_mode=cfg["model"]["attn_mode"])
    x = jax.ShapeDtypeStruct(
        (job["batch_size"], job["window"], model.features), jnp.float32)
    held = (4 + (m.loop_steps > 1)) * sum(
        p.size * p.dtype.itemsize for p in jax.tree.leaves(
            jax.eval_shape(model.init, jax.random.PRNGKey(0), x)))
    tokens = x.shape[0] * x.shape[1]
    backward = hybrid.backward_bytes(m, tokens, 4)
    assert backward == ("kda" in m.layer_types) * 3_221_225_472
    budget = hybrid.remat_budget(
        _CHIP_BYTES, held, sum(model._kept_bytes(x).values()), backward)
    taken = hybrid.budget_takes(
        hybrid.budget_candidates(m, tokens, 4), budget)
    got = {name: tuple(c.layer for c in taken if c.name == name)
           for name in hybrid.BUDGETED}
    assert got == dict(outs, ffn_hidden=first_product)
    if not m.post_norms:
        assert first_product == hybrid.kept_layers(
            hybrid.ffn_hidden_bytes(m, tokens, 4), budget)
    else:
        assert sum(c.bytes for c in taken if c.name == "ffn_out") \
            == 1_610_612_736
