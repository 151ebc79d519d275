"""The shapes of stack `models.hybrid.SensorHybrid` is tested in, stated
once: a row a shape — the benchmark's seven hybrid configurations at a
tiny preset, each with its plain reference (loaded by path, as
`benchmark/tests` loads it), and a sandwich stack that is no loop, which
has none — with the helpers the stack tests share and the programs they
share: within a worker process a (stack, mode, keep)'s gradient, the
counts of its jaxpr and the registry it left are made once
(`policy_run`), as is a reference's gradient (`reference_gradient`).
Read by `test_stack_contract.py`, `test_remat_policy.py` and the six
stack files; not collected."""

import functools
import importlib.util
import json
import os
import re
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.models import hybrid
from iotml.models.hybrid import HybridConfig, SensorHybrid
from iotml.obs.metrics import default_registry
from iotml.train.loop import make_loss_fn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")


class Stack(NamedTuple):
    """A shape of stack: the reference under `benchmark/configs/` (None:
    `config` states it by hand), the tiny preset that `use()`s it, and
    what the tests count of it."""

    stem: Optional[str]
    tiny: dict             # the sizes the preset states, by the file's keys
    published: dict = {}   # and of the file's `published` (routed over)
    layers: int = 0        # the file's `layer_types`, cut to the first
    window: int = 40       # positions of a test's batch
    attention: int = 0     # layers that run the flash kernels
    routed: int = 0        # expert layers
    ops: str = ""          # `benchmark/<ops>.py` counts its parameters
    unsettled: bool = False   # compare at norms' weights that are not one
    fit_seeds: tuple = (1, 2)   # a compiled job's batches
    moments_rtol: float = 2e-4  # how close Adam's moments come
    update_rtol: float = 2e-3   # and the parameters' change
    config: Optional[HybridConfig] = None


STACKS = {
    # width 64, 4 heads of 16 over 2 key/value heads, 4 state heads of 16
    # (hence the expansion of 1), state 8, chunk 8; 21 positions: no
    # multiple of the chunk
    "granite": Stack(
        "granite-4.0-h-micro",
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             shared_intermediate_size=128, mamba_n_heads=4, mamba_d_head=16,
             mamba_expand=1, mamba_d_state=8, mamba_chunk_size=8,
             num_hidden_layers=3,
             layer_types=["mamba", "attention", "mamba"]),
        window=21, attention=1, ops="hybrid_ops", fit_seeds=(0, 1, 2, 3),
        moments_rtol=2e-3),
    # width 64, 4 heads of 16 + 8 rotary beside 16, latent 32; 16 experts
    # of 24, 3 a token, 4 held, one shared; a dense layer and two that route
    "kimi": Stack(
        "kimi-vl-a3b-instruct",
        dict(hidden_size=64, num_attention_heads=4, intermediate_size=96,
             moe_intermediate_size=24, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3,
             n_routed_experts=4, num_experts_per_tok=3, n_shared_experts=1),
        published=dict(n_routed_experts=16), attention=3, routed=2,
        ops="moe_ops"),
    # width 64; the share held: 4 state heads of 8 in one group, state 8;
    # 2 query heads of 16 on one key/value head; 16 experts of 24 in a
    # latent of 32, 5 a token, 4 held, a shared expert of 48; `M E * E M`
    "nemotron": Stack(
        "nemotron-3-super-120b-a12b",
        dict(hidden_size=64, mamba_num_heads=4, mamba_head_dim=8,
             ssm_state_size=8, n_groups=1, chunk_size=8,
             num_attention_heads=2, num_key_value_heads=1, head_dim=16,
             moe_latent_size=32, moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=48, n_routed_experts=4,
             num_experts_per_tok=5, num_hidden_layers=5,
             hybrid_override_pattern="ME*EM"),
        published=dict(n_routed_experts=16), attention=1, routed=2,
        ops="latent_moe_ops"),
    # width 64; 4 query heads of 16 over 2 key/value heads; a dense MLP of
    # 96; 16 experts of 24, 3 a token, 4 held; the file's five layers,
    # `c A c c c`, the first with the dense MLP
    "lfm2": Stack(
        "lfm2-24b-a2b",
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=96, moe_intermediate_size=24, num_experts=4,
             num_experts_per_tok=3),
        published=dict(num_experts=16), attention=1, routed=4,
        ops="short_conv_ops"),
    # width 64; 4 heads of 16 on 4 key/value heads; an MLP of 96; two
    # sandwich-normed layers, run the file's four times
    "ouro": Stack(
        "ouro-2.6b",
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_dim=16, intermediate_size=96, num_hidden_layers=2),
        layers=2, attention=2, ops="loop_ops", unsettled=True),
    # width 64; 4 query heads of 16 over 2 key/value heads; the file's
    # four layers, `G W W W`: a global layer without positions, three
    # that turn their heads and meet the last 24 keys (40 positions: past
    # the window and no multiple of a block); 16 ReLU-gated experts of
    # 24, 3 a token, 4 held, routed on the block's own input
    "smallthinker": Stack(
        "smallthinker-21b-a3b",
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_ffn_hidden_size=24, moe_num_primary_experts=4,
             moe_num_active_primary_experts=3, sliding_window_size=24),
        published=dict(moe_num_primary_experts=16), attention=4, routed=4,
        ops="window_ops"),
    # width 64; 4 delta-rule heads of 16 under gates of that rank, chunks of
    # 16 (40 positions: no multiple of one); 4 latent heads of 16 + 8
    # beside 16 over a latent of 32, WITHOUT positions; an MLP of 96; 16
    # experts of 24, 3 a token, 4 held, one shared; the file's five
    # layers, `K K K L K`, the first with the dense MLP
    "kimi_linear": Stack(
        "kimi-linear-48b-a3b",
        dict(hidden_size=64, num_attention_heads=4, intermediate_size=96,
             moe_intermediate_size=24, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
             num_experts_per_token=3, kda_chunk_size=16,
             linear_attn_config=dict(
                 full_attn_layers=[4], kda_layers=[1, 2, 3, 5], head_dim=16,
                 num_heads=4, short_conv_kernel_size=4)),
        # Adam's early steps are the rate times g / |g|: behind the L2
        # norms q's and k's columns hold gradients of the order of their
        # rounding, whose steps are noise (3e-3 of the leaf's largest,
        # both moments within 2e-4)
        published=dict(num_experts=16), attention=1, routed=4,
        ops="delta_ops", update_rtol=5e-3),
    # sandwich norms without the loop: no configuration's, by hand
    "sandwich": Stack(None, {}, attention=1, config=HybridConfig(
        layer_types=("mamba", "attention"), post_norms=True)),
}
#: the rows that have a plain reference
REFERENCED = tuple(name for name, row in STACKS.items() if row.stem)
MODES = ("dense", "flash_interpret")


def load(stem: str, name: str):
    """(a module of its own of `benchmark/configs/sensorformer-<stem>.py`
    under `name`, the file's configuration as it is published)."""
    path = os.path.join(CONFIGS, f"sensorformer-{stem}")
    with open(path + ".json") as fh:
        return load_module(name, path + ".py"), json.load(fh)


def load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(stack: str, name: str, published=None, **sizes):
    """(reference, configuration) of a row at its tiny preset, with
    `sizes` over it, `use()`d: a module of the caller's own."""
    row = STACKS[stack]
    mod, cfg = load(row.stem, name)
    cfg.update(row.tiny)
    if row.published:
        cfg["published"] = dict(cfg["published"],
                                **(published or row.published))
    if row.layers:
        cfg["layer_types"] = cfg["layer_types"][:row.layers]
    cfg["job"] = dict(cfg["job"], window=row.window)
    cfg.update(sizes)
    mod.use(cfg)
    return mod, cfg


@functools.lru_cache(maxsize=None)
def reference(stack: str):
    """A row's plain reference at the tiny preset, one a process: what
    a test changes in its configuration it puts back."""
    return tiny(stack, f"bench_{stack}_reference")


def config(stack: str) -> HybridConfig:
    row = STACKS[stack]
    if row.config is not None:
        return row.config
    mod, cfg = reference(stack)
    return mod.hybrid_config(cfg)


def batch(B=2, T=40, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, T, 18)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, 1, 18)), jnp.float32),
            jnp.ones((B,), jnp.float32))


def stream(B=2, T=40, d=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(B, T, d)),
                       jnp.float32)


def close(got, want, rtol=2e-4):
    """Within `rtol` of the reference's largest entry, leaf by leaf."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(jnp.abs(w).max()), 1e-30)
        assert float(jnp.abs(g - w).max()) <= rtol * scale


def value_and_grads(f, p, u):
    """A weighted sum of f(p, u) and its gradients in p and u."""
    w = stream(*u.shape, seed=99)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, u: jnp.sum(w * f(p, u)), argnums=(0, 1)))(p, u)


def unsettled(params, seed: int):
    """`params` with norms' weights that are not all one and a gate's
    bias that is not zero: a norm on the wrong operand, or a bias left
    out, would not hide."""
    rng = np.random.default_rng(seed)

    def unsettle(path, leaf):
        names = [k.key for k in path]
        if names[-1] == "scale" or names[-2:] == ["exit_gate", "bias"]:
            return leaf + jnp.asarray(rng.uniform(-0.5, 0.5, leaf.shape),
                                      jnp.float32)
        return leaf

    return jax.tree_util.tree_map_with_path(unsettle, params)


@functools.lru_cache(maxsize=None)
def params(stack: str, seed: int):
    """A row's seeded weights: the reference's, or the model's own."""
    row = STACKS[stack]
    if row.stem is None:
        return SensorHybrid(row.config).init(
            jax.random.PRNGKey(seed), batch(T=row.window)[0])["params"]
    made = reference(stack)[0].init_params(seed)
    return unsettled(made, seed) if row.unsettled else made


def shapes(tree):
    return jax.tree.map(jnp.shape, tree)


def parameters(shape_tree) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shape_tree, is_leaf=lambda s: isinstance(s, tuple)))


# ------------------------------------------------- a compiled job, tiny
def jobs(batches, n_valid=2):
    from iotml.data.dataset import Batch

    return [Batch(x=np.asarray(x), y=np.asarray(y), n_valid=n_valid,
                  first_index=0) for x, y, _ in batches]


def seeded_trainer(model, weights, x, learning_rate=1e-3):
    """A `Trainer` whose state starts from a COPY of `weights` (the fit
    donates its state)."""
    from iotml.train.loop import Trainer

    trainer = Trainer(model, supervised=True, learning_rate=learning_rate)
    trainer._ensure_state(x)
    trainer.state = trainer.state.replace(
        params=jax.tree.map(jnp.array, weights))
    return trainer


def tiny_fit(model, patch, steps=3, epochs=2, T=40):
    """A fresh trace of a tiny compiled job of `steps` equal batches →
    (the fit's history, the registry before and after, the
    `device_get`s the fit made); `patch` is the caller's monkeypatch."""
    from iotml.train import loop

    jax.clear_caches()
    before = default_registry.collect()
    gets = []
    device_get = jax.device_get
    patch.setattr(loop.jax, "device_get",
                  lambda t: gets.append(1) or device_get(t))
    history = loop.Trainer(model, supervised=True, learning_rate=1e-5) \
        .fit_compiled(jobs([batch(T=T)] * steps), epochs=epochs)
    made, said = len(gets), default_registry.collect()
    assert history["fit"] == "scanned" and np.isfinite(history["loss"]).all()
    return history, before, said, made


def scopes_in_the_program(model, weights, x, scopes):
    """The named scopes ride the program's operations."""
    text = jax.jit(lambda p: model.apply(
        {"params": p}, x, mutable=["reports"])[0]).lower(
            weights).as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    return text


# --------------------------------------- what the recomputation keeps
REMAT_GAUGES = ("kept_bytes", "kept_layers", "keepable_layers")


def remat_gauges(said: dict) -> dict:
    """Every `iotml_remat_*` series of a registry's snapshot."""
    return {k: v for k, v in said.items() if k.startswith("iotml_remat_")}


def only_these_kinds_are_kept(said: dict, *kinds: str) -> None:
    """Every kind of `models.hybrid.TABLE` but `kinds` — the ones the
    calling stack has, whose numbers its own test works out by hand —
    reads 0 in all three `iotml_remat_*` gauges."""
    table = {row.kind for row in hybrid.TABLE}
    assert set(kinds) <= table
    for kind in table - set(kinds):
        for what in REMAT_GAUGES:
            assert said.get(f'iotml_remat_{what}{{kind="{kind}"}}', 0) == 0, \
                (what, kind)


def policy_names(model, x) -> dict:
    """layer → the names its recomputation's policy lists, in a fresh
    forward trace of `model` (shapes alone, with parameters to count)."""
    listed, of = {}, {}
    plain_remat, plain_names = nn.remat, \
        jax.checkpoint_policies.save_only_these_names

    def names(*kept):
        policy = plain_names(*kept)
        of[policy] = kept
        return policy

    def remat(target, policy=None):
        made = plain_remat(target, policy=policy)
        if policy not in of:
            return made

        def build(*a, name, **k):
            listed[name] = of[policy]
            return made(*a, name=name, **k)
        return build

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax.checkpoint_policies, "save_only_these_names", names)
        patch.setattr(hybrid.nn, "remat", remat)
        weights = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                 x)["params"]
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, x, mutable=["reports"]), weights)
    return listed


#: the layers whose feed-forward product the budget is held to take —
#: and `plain`: no policy at all, every block under plain `nn.remat`
KEEPS = ("all", "last", "none")
PLAIN = "plain"


def hold_budget(patch, cfg, x, keep) -> tuple:
    """The byte budget set to what keeps the feed-forward part's first
    product as `keep` says — `all`: every candidate; `last`: that
    product in the last layer that makes one, after what is dearer than
    it; `none`: nothing.  → the layers that then keep that product, the
    layers that make one, and all the budget took."""
    candidates = hybrid.budget_candidates(cfg, x.shape[0] * x.shape[1],
                                          x.dtype.itemsize)
    first = [c for c in candidates if c.name == hybrid.FFN_KEPT and c.bytes]
    makes = tuple(c.layer for c in first)
    # a stack of expert layers without a shared expert makes no first
    # product: whatever the budget, nothing is bought
    dearer = [c for c in candidates
              if first and c.density > first[-1].density]
    budget = {"all": sum(c.bytes for c in candidates), "none": 0,
              "last": sum(c.bytes for c in dearer + first[-1:])}[keep]
    patch.setattr(hybrid, "remat_budget", lambda *sizes: budget)
    taken = hybrid.budget_takes(candidates, budget)
    keeps = tuple(c.layer for c in taken if c.name == hybrid.FFN_KEPT)
    assert keeps == {"all": makes, "last": makes[-1:], "none": ()}[keep]
    assert set(taken) == {"all": {c for c in candidates if c.bytes},
                          "last": set(dearer) | set(first[-1:]),
                          "none": set()}[keep]
    # a sandwich block's feed-forward output is dearer than the product
    assert bool(dearer) == cfg.post_norms
    return keeps, makes, taken


def count(jaxpr, found, counts):
    """Equations of `jaxpr` and of every jaxpr inside it, by `found` —
    a Pallas call counted as the one equation it is to the model: what
    its kernel computes inside (the delta rule's float32 products at
    `highest`, say) is not the model's."""
    for eqn in jaxpr.eqns:
        kind = found(eqn)
        if kind:
            counts[kind] = counts.get(kind, 0) + 1
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count(sub, found, counts)
    return counts


def what(assignments):
    """What `count` counts of an equation; a `gather` or `scatter-add`
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
            if re.search(r"\bmlp_out\b", where):
                return "ffn_out:" + re.search(r"\blayer(\d+)\b", where)[1]
            precision = eqn.params["precision"]
            return "highest" if precision is not None and all(
                p == jax.lax.Precision.HIGHEST for p in precision) else None
        if name in ("gather", "scatter-add"):
            aval = eqn.invars[0].aval
            return name if aval.shape == (assignments,) and jnp.issubdtype(
                aval.dtype, jnp.floating) else None
        return name if name in ("top_k", "sort") else None
    return found


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


def saved(jaxpr, name, found):
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
            saved(sub, name, found)
    return found


def nbytes(avals) -> int:
    return sum(a.size * a.dtype.itemsize for a in avals)


class Run(NamedTuple):
    """What one trace and one run of a row's loss and gradient left."""

    keeps: tuple     # `hold_budget`'s three; None under `plain`
    makes: tuple
    taken: tuple
    loss: float
    grads: dict
    reports: tuple   # the loss's reports: expert layers', an objective's
    counts: dict     # the gradient's jaxpr, by `what`
    saved: dict      # name → the avals of that name read back
    stacked_streams: int   # bytes the forward scan stacks at the stream's size
    said: dict       # the registry right after the trace


@functools.lru_cache(maxsize=None)
def policy_run(stack: str, mode: str, keep: str, precision=None) -> Run:
    """The loss and its gradient of a row's model at the row's seeded
    weights, with the byte budget held as `keep` says (`hold_budget`) or
    every block under plain `nn.remat` (`plain`), products at
    `precision` (None: the backend's default, under which the router's
    `highest` product stands out): ONE fresh trace, whose jaxpr is
    counted and whose program is compiled and run once."""
    cfg, row = config(stack), STACKS[stack]
    model = SensorHybrid(cfg, attn_mode=mode)
    data = batch(T=row.window)
    weights = params(stack, 3)
    loss = make_loss_fn(model, supervised=True)
    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision(precision):
        held = (None, None, None)
        if keep == PLAIN:
            plain = nn.remat
            patch.setattr(hybrid.nn, "remat",
                          lambda target, policy=None: plain(target))
        else:
            held = hold_budget(patch, cfg, data[0], keep)
        jax.clear_caches()   # a fresh trace is what sets the gauges
        traced = jax.jit(jax.value_and_grad(loss, has_aux=True)).trace(
            weights, *data)
        said = default_registry.collect()
        (value, aux), grads = traced.lower().compile()(weights, *data)
    jaxpr = traced.jaxpr.jaxpr
    names = (*hybrid.BUDGETED, "flash_out", "flash_lse")
    forward = next((e for e in jaxpr.eqns if e.primitive.name == "scan"),
                   None)
    of_stream = (cfg.loop_steps,) + data[0].shape[:2] + (cfg.d_model,)
    return Run(*held, float(value), grads, aux[2:],
               count(jaxpr, what(data[0].shape[0] * data[0].shape[1]
                                 * cfg.top_k), {}),
               {name: tuple(saved(jaxpr, name, [])) for name in names},
               0 if forward is None or cfg.loop_steps == 1 else nbytes(
                   v.aval for v in forward.outvars
                   if v.aval.shape == of_stream),
               said)


@functools.lru_cache(maxsize=None)
def reference_gradient(stack: str):
    """(loss, gradients) of a row's plain reference at `params(stack, 3)`
    on `batch(T=row.window)`."""
    mod, _ = reference(stack)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(mod.loss_fn))(
            params(stack, 3), *batch(T=STACKS[stack].window))
