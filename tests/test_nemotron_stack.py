"""The one-part-a-layer stack (`models.hybrid.SensorHybrid` with `none`
for a layer's mixer or its feed-forward part, a share of the heads, and
experts in a latent) against the benchmark's plain reference
(`stacks.reference`): the chip's-share cut of all three kinds of layer
(the shares add up to the uncut layer, which has grouped B and C and the
group norm), the accepted configurations' parameter trees, and what a
fit says of the latent and the tiles.  The model and one compiled job
against the reference are the `nemotron` cases of
`test_stack_contract.py`.  All at a tiny preset on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from iotml.models import hybrid
from iotml.models.hybrid import HybridConfig, SensorHybrid
from iotml.models.latent_moe import ExpertLayer
from iotml.ops import moe
from stacks import batch as _batch
from stacks import close as _close


@pytest.fixture(scope="module")
def ref():
    """The configuration's plain reference at the tiny preset."""
    return stacks.reference("nemotron")


# --------------------------------------------- the model and the reference
def test_a_layer_builds_the_norm_of_the_part_it_has(ref):
    """`M E * E M`: a mixer's layer holds `norm1` and `mixer`, an expert
    layer `norm2` and `moe` with the two latent projections and non-gated
    experts of the latent's width — the reference's tree, shape by shape."""
    mod, cfg = ref
    model = SensorHybrid(mod.hybrid_config(cfg))
    shapes = jax.tree.map(jnp.shape, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), _batch()[0])["params"])
    assert shapes == jax.tree.map(jnp.shape, mod.init_params(3))
    assert [sorted(shapes[f"layer{i}"]) for i in range(5)] == [
        ["mixer", "norm1"], ["moe", "norm2"], ["mixer", "norm1"],
        ["moe", "norm2"], ["mixer", "norm1"]]
    assert shapes["layer1"]["moe"]["experts_in"] == (4, 32, 24)
    assert shapes["layer1"]["moe"]["experts_out"] == (4, 24, 32)
    assert shapes["layer1"]["moe"]["latent_in"]["kernel"] == (64, 32)
    assert shapes["layer1"]["moe"]["shared_in"]["kernel"] == (64, 48)
    # the heads held are a share: their width is the file's, not 64 / 2
    assert shapes["layer2"]["mixer"]["q"]["kernel"] == (64, 32)
    assert shapes["layer2"]["mixer"]["k"]["kernel"] == (64, 16)
    with pytest.raises(ValueError, match="one of each a layer"):
        SensorHybrid(HybridConfig(layer_types=("none",),
                                  ffn_types=("none",))).init(
            jax.random.PRNGKey(0), _batch()[0])
    with pytest.raises(ValueError, match="experts are distinct"):
        ExpertLayer(HybridConfig(experts=4, experts_held=(0, 4),
                                 top_k=5)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


# ------------------------------------------------------- the chip's share
def test_the_shares_add_up_to_the_uncut_layers():
    """The model at a small size — 2 B/C groups, 2 key/value heads, 8
    experts — and its cut into 2 x 2 shares: each Mamba group's partial
    `W_out` product, each head group's partial `W_o` product, each expert
    range's routed term through `W_back`, with the shared expert, the
    router and the latent projections counted once, add up to the uncut
    reference's layer, which has grouped B and C and the group norm."""
    whole, cfg = stacks.tiny(
        "nemotron", "bench_nemotron_uncut", mamba_num_heads=8, n_groups=2,
        num_attention_heads=4, num_key_value_heads=2, n_routed_experts=8,
        hybrid_override_pattern="M*E", num_hidden_layers=3)
    cfg["published"]["n_routed_experts"] = 8
    whole.use(cfg)
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.normal(size=(2, 40, 64)), jnp.float32)
    p = jax.jit(lambda k: whole._init(k))(jax.random.PRNGKey(11))
    share_cfg = dict(cfg, mamba_num_heads=4, n_groups=1,
                     num_attention_heads=2, num_key_value_heads=1,
                     n_routed_experts=4)
    program = whole.hybrid_config(dict(share_cfg, experts_held={"first": 0}))
    whole.use(cfg)

    def cut(v, sizes, g, axis=-1):
        """Group g's part of each run of `sizes` along `axis`."""
        runs = jnp.split(v, np.cumsum(sizes)[:-1], axis=axis)
        return jnp.concatenate(
            [jnp.split(r, 2, axis=axis)[g] for r in runs], axis=axis)

    with jax.default_matmul_precision("highest"):
        # M: in_proj's columns are [z, x, B_0 B_1, C_0 C_1, dt]
        m = p["layer0"]["mixer"]
        want = whole._mamba(m, u)
        total = jnp.zeros_like(u)
        for g in (0, 1):
            share = {
                "in_proj": {"kernel": cut(m["in_proj"]["kernel"],
                                          (64, 64, 16, 16, 8), g)},
                "conv_kernel": cut(m["conv_kernel"], (64, 16, 16), g),
                "conv_bias": cut(m["conv_bias"], (64, 16, 16), g),
                "dt_bias": cut(m["dt_bias"], (8,), g),
                "A_log": cut(m["A_log"], (8,), g), "D": cut(m["D"], (8,), g),
                "norm": {"scale": cut(m["norm"]["scale"], (64,), g)},
                "out_proj": {"kernel": cut(m["out_proj"]["kernel"], (64,), g,
                                           axis=0)}}
            total = total + hybrid.MambaMixer(program).apply(
                {"params": share}, u)
        _close(total, want, rtol=1e-5)

        # *: query heads 2g, 2g+1 read key/value head g
        a = p["layer1"]["mixer"]
        want = whole._attention(a, u)
        total = jnp.zeros_like(u)
        for g in (0, 1):
            share = {"q": {"kernel": cut(a["q"]["kernel"], (64,), g)},
                     "k": {"kernel": cut(a["k"]["kernel"], (32,), g)},
                     "v": {"kernel": cut(a["v"]["kernel"], (32,), g)},
                     "o": {"kernel": cut(a["o"]["kernel"], (64,), g, axis=0)}}
            total = total + hybrid.GroupedAttention(program, "dense").apply(
                {"params": share}, u)
        _close(total, want, rtol=1e-5)

        # E: every share routes over all eight, and alike
        e = p["layer2"]["moe"]
        want, counts = whole._latent_moe(e, u)
        shared = whole._relu2(u, e["shared_in"]["kernel"],
                              e["shared_out"]["kernel"])
        total = jnp.zeros_like(u)
        for first in (0, 4):
            share = dict(e, experts_in=e["experts_in"][first:first + 4],
                         experts_out=e["experts_out"][first:first + 4])
            layer = ExpertLayer(whole.hybrid_config(dict(
                share_cfg, experts_held={"first": first})))
            whole.use(cfg)
            out, reports = layer.apply({"params": share}, u,
                                       mutable=["reports"])
            assert np.array_equal(
                reports["reports"]["expert_counts"], counts)
            total = total + (out - shared)
        assert int(counts.sum()) == 2 * 40 * 5
        _close(total + shared, want, rtol=1e-5)


# ----------------------------------------- the accepted configurations
GRANITE_TREE = {
    "mamba": {"A_log": (64,), "D": (64,), "conv_bias": (4352,),
              "conv_kernel": (4, 4352), "dt_bias": (64,),
              "in_proj": {"kernel": (2048, 8512)}, "norm": {"scale": (4096,)},
              "out_proj": {"kernel": (4096, 2048)}},
    "attention": {"q": {"kernel": (2048, 2048)}, "k": {"kernel": (2048, 512)},
                  "v": {"kernel": (2048, 512)}, "o": {"kernel": (2048, 2048)}}}
KIMI_MIXER = {"q": {"kernel": (2048, 3072)}, "kv_a": {"kernel": (2048, 576)},
              "kv_norm": {"scale": (512,)}, "kv_b": {"kernel": (512, 4096)},
              "o": {"kernel": (2048, 2048)}}
KIMI_MOE = {"router": (2048, 64), "router_bias": (64,),
            "experts_in": (8, 2048, 2816), "experts_out": (8, 1408, 2048),
            "shared_in": {"kernel": (2048, 5632)},
            "shared_out": {"kernel": (2816, 2048)}}


NEMOTRON_TREE = {
    "M": {"norm1": {"scale": (4096,)}, "mixer": {
        "A_log": (16,), "D": (16,), "conv_bias": (1280,),
        "conv_kernel": (4, 1280), "dt_bias": (16,),
        "in_proj": {"kernel": (4096, 2320)}, "norm": {"scale": (1024,)},
        "out_proj": {"kernel": (1024, 4096)}}},
    "*": {"norm1": {"scale": (4096,)}, "mixer": {
        "q": {"kernel": (4096, 512)}, "k": {"kernel": (4096, 128)},
        "v": {"kernel": (4096, 128)}, "o": {"kernel": (512, 4096)}}},
    "E": {"norm2": {"scale": (4096,)}, "moe": {
        "router": (4096, 512), "router_bias": (512,),
        "latent_in": {"kernel": (4096, 1024)},
        "latent_out": {"kernel": (1024, 4096)},
        "experts_in": (8, 1024, 2688), "experts_out": (8, 2688, 1024),
        "shared_in": {"kernel": (4096, 5376)},
        "shared_out": {"kernel": (5376, 4096)}}}}


@pytest.mark.parametrize("name,parameters", [
    ("sensorformer-granite-4.0-h-micro", 746_546_130),
    ("sensorformer-kimi-vl-a3b-instruct", 585_080_146),
    ("sensorformer-nemotron-3-super-120b-a12b", 566_799_362)])
def test_the_accepted_configurations_trees_are_what_they_were(name,
                                                              parameters):
    """Path by path and shape by shape, at the published widths: the
    layers that have a mixer AND a feed-forward part build what they
    built before a layer could be one part, a head a share, an expert
    another form — and all three what they built before grouped
    attention could norm and turn its heads, a mixer be a gated short
    convolution, an expert layer go without its shared expert."""
    mod, cfg = stacks.load(name.removeprefix("sensorformer-"),
                           "bench_tree_" + name.split("-")[1])
    mod.use(cfg)
    model = SensorHybrid(mod.hybrid_config(cfg))
    assert not model.cfg.qk_norm and model.cfg.attn_rope_theta == 0 \
        and "short_conv" not in model.cfg.layer_types
    if "nemotron" not in name:
        assert model.cfg.head_dim == 0 and model.cfg.moe_latent == 0 \
            and model.cfg.expert_form == "gated_silu"
    tree = jax.tree.map(jnp.shape, jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 16, 18)))["params"])
    width = cfg["hidden_size"]
    norm = {"scale": (width,)}
    want = {"embed": {"kernel": (18, width), "bias": (width,)},
            "head": {"kernel": (width, 18), "bias": (18,)}, "norm_f": norm}
    if "nemotron" in name:
        assert model.cfg.shared_dim == 5376
        for i, letter in enumerate(cfg["hybrid_override_pattern"]):
            want[f"layer{i}"] = NEMOTRON_TREE[letter]
    elif "granite" in name:
        for i, kind in enumerate(cfg["layer_types"][:10]):
            want[f"layer{i}"] = {
                "norm1": norm, "norm2": norm, "mixer": GRANITE_TREE[kind],
                "mlp_in": {"kernel": (2048, 16384)},
                "mlp_out": {"kernel": (8192, 2048)}}
    else:
        for i in range(6):
            want[f"layer{i}"] = {"norm1": norm, "norm2": norm,
                                 "mixer": KIMI_MIXER}
            want[f"layer{i}"].update(
                {"moe": KIMI_MOE} if i else
                {"mlp_in": {"kernel": (2048, 22528)},
                 "mlp_out": {"kernel": (11264, 2048)}})
    assert tree == want
    assert stacks.parameters(tree) == parameters
    assert tree == jax.tree.map(jnp.shape, jax.eval_shape(
        lambda: mod.init_params(0)))


# ------------------------------------------------------- what engaged
def test_a_tiny_fit_says_what_engaged(ref, monkeypatch):
    """The trace-time counters after a fit — the layers by kind, the
    latent's width — the `latent_proj` scope in the fit's program, and
    the data read back with the losses at the fit's one sync: the rows of
    the live tiles by whether they hold an assignment."""
    from iotml.obs.metrics import default_registry

    mod, cfg = ref
    monkeypatch.setattr(moe, "TILE", 16)
    x = _batch()[0]
    model = SensorHybrid(mod.hybrid_config(cfg))
    history, before, got, gets = stacks.tiny_fit(model, monkeypatch)
    assert gets == 1          # the reports came back with the losses
    assert [got[f'iotml_model_layers{{kind="{k}"}}'] for k in
            ("mamba", "attention", "mla", "dense_ffn", "moe_ffn")] \
        == [2, 1, 0, 0, 2]
    assert got["iotml_remat_blocks"] == 5
    # what the blocks' recomputation keeps, a step: two layers' selection
    # and plan (four [80, 5] arrays with the sorted weights, three fields
    # of 24 tiles of 16 rows, the live tiles' count, `counts`) and their
    # routed sums [80, 32]
    assert moe.plan_kept_bytes(80, 5, 4, 16) == 4 * (4 * 400 + 3 * 24 + 1 + 16)
    assert [got[f'iotml_remat_kept_bytes{{kind="{k}"}}'] for k in
            ("router", "experts")] \
        == [2 * moe.plan_kept_bytes(80, 5, 4, 16), 2 * 80 * 32 * 4]
    # and under the byte budget the two shared experts' first product,
    # [80, 48] each (non-gated: one product's width)
    assert got['iotml_remat_kept_bytes{kind="ffn"}'] == 2 * 80 * 48 * 4
    assert got['iotml_remat_kept_layers{kind="ffn"}'] \
        == got['iotml_remat_keepable_layers{kind="ffn"}'] == 2
    # and nothing else: `dense` attention ran no kernel
    stacks.only_these_kinds_are_kept(got, "router", "experts", "ffn")
    assert got["iotml_moe_latent_dim"] == 32
    assert got['iotml_moe_experts{kind="held"}'] == 4
    assert got['iotml_moe_experts{kind="routed_over"}'] == 16
    assert got["iotml_moe_top_k"] == 5
    assert got["iotml_moe_dispatch_rows"] == moe.dispatch_rows(80, 5, 4)
    assert got["iotml_moe_plan_sorted_operands"] == 3
    moved = lambda name, kind: got[f'{name}{{kind="{kind}"}}'] \
        - before.get(f'{name}{{kind="{kind}"}}', 0.0)  # noqa: E731
    steps = np.concatenate([np.asarray(c).reshape(-1, 16)[:, :4]
                            for c in jax.tree.leaves(history["reports"])])
    live = moved("iotml_moe_tile_rows_total", "live")
    assert live == steps.sum() == moved("iotml_moe_assignments_total", "held")
    assert live > 0
    # every expert held walks whole tiles of 16 rows, a step and layer
    assert moved("iotml_moe_tile_rows_total", "padding") \
        == (-(-steps // 16) * 16).sum() - live > 0
    stacks.scopes_in_the_program(
        model, mod.init_params(1), x,
        ("latent_proj", "router", "experts", "shared", "ssm_proj", "ssd",
         "conv", "attn"))
    # a layer that acts at full width says 0
    jax.clear_caches()
    ExpertLayer(HybridConfig()).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8, 64)))
    assert default_registry.collect()["iotml_moe_latent_dim"] == 0
