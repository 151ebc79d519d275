"""The cell `st-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the cell's entries
found by name, the file against the source's row, the operations' count
against a brute-force count at a tiny shape and the two readers it
feeds (each silent for every other configuration), the contract's line,
every per-layer metric the cell lists, the reference's uncut expert
layer against its shares, the experts placed on the first job's first
batch, the three planted faults caught, and the lower-precision control
coming out as not correct."""

import contextlib
import dataclasses
import io
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, window_ops

ROOT = harness.ROOT
CELL = "st-train-backlog"
CONFIG = "sensorformer-smallthinker-21b-a3b"
TINY = ["cfg.hidden_size=64", "cfg.num_attention_heads=4",
        "cfg.num_key_value_heads=2", "cfg.head_dim=16",
        "cfg.moe_ffn_hidden_size=24", "cfg.moe_num_primary_experts=4",
        "cfg.published.moe_num_primary_experts=16",
        "cfg.moe_num_active_primary_experts=3",
        "cfg.sliding_window_size=24", "cfg.job.window=64"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}
SETUP_METRICS = {"import_s.setup", "backend_s.setup", "state_init_s.setup",
                 "first_fit_s.setup", "trace_lower_s.setup",
                 "compile_s.setup", "cache_misses.setup"}
OWN = {"train_mfu.window": "program_span",
       "attn_band_fill.train": "program_counter"}
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
GAPS = {"epoch_loss_gap", "moment_norm_gap", "update_norm_gap",
        "update_leaf_gap", "assignment_flip_share"}


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _config():
    entry = next(c for c in _bench()["configs"] if c["name"] == CONFIG)
    return entry, harness.load_json(os.path.join(ROOT, entry["file"]))


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def _rehearse(trace: int, seed: int):
    import benchmark.run as bench_run

    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--set", "log_scale=0.05"]
    for item in TINY:
        argv += ["--set", item]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert bench_run.main(argv) == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


def _failed(lines):
    return [ln.split(":")[0][len("check "):] for ln in lines
            if ln.startswith("check ") and ln.endswith("NOT CORRECT")]


def test_the_cells_entries_are_found_by_name():
    """By name, not by place: later PRs append behind them."""
    bench = _bench()
    cell = harness.find_cell(bench, CELL)
    assert cell["config"] == CONFIG
    assert cell["chips"] == 1 and cell["traffic"] == "train_backlog"
    assert len(cell["why"]) <= 200 and "4x" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | set(OWN)
    for name, source in OWN.items():
        assert next(m for m in bench["per_layer"] if m["name"] == name) == {
            "name": name, "unit": "%", "better": "higher", "source": source,
            "layer": "fit program", "moves": "train_tokens_per_s",
            "workloads": [CELL]}
    # what the benchmark had is as it was
    for name, cells in (("train_mfu", ["sf-train-backlog"]),
                        ("train_mfu.hybrid", ["gh-train-backlog"]),
                        ("train_mfu.moe", ["km-train-backlog"]),
                        ("train_mfu.latent_moe", ["ns-train-backlog"]),
                        ("moe_tile_fill.train", ["ns-train-backlog"]),
                        ("train_mfu.short_conv", ["lf-train-backlog"]),
                        ("train_mfu.loop", ["ou-train-backlog"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == cells
    assert [w["name"] for w in bench["workloads"]][:6] == [
        "sf-train-backlog", "gh-train-backlog", "km-train-backlog",
        "ns-train-backlog", "lf-train-backlog", "ou-train-backlog"]


def test_the_file_holds_the_sources_config_but_for_the_cuts():
    """Every key of the catalog's row under its own name and at its
    published value; `reduced` names the four that differ — depth, the
    two layouts cut to the layers held, the experts held: no width and
    no head count — and the file states the published values and the
    deployment beside them."""
    entry, cfg = _config()
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["source_url"] == cfg["source"])
        differ = {k for k, v in row["config"].items() if cfg.get(k, k) != v}
        assert differ == set(REDUCED) and set(row["config"]) <= set(cfg)
        assert cfg["published"] == dict(
            {k: row["config"][k] for k in REDUCED},
            deployment=cfg["published"]["deployment"])
        # published layers 0-3: one whole period
        for key in ("rope_layout", "sliding_window_layout"):
            assert cfg[key] == row["config"][key][:4]
    assert "four chips share each layer" in cfg["published"]["deployment"]
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["experts_held"]["first"]) == (4, 16, 0)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"],
            cfg["moe_num_active_primary_experts"],
            cfg["sliding_window_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["max_position_embeddings"],
            cfg["published"]["moe_num_primary_experts"]) \
        == (2560, 28, 4, 128, 768, 6, 4096, 1500000, 1e-6, 16384, 64)
    assert cfg["model"]["parameters"] == window_ops.parameters(cfg) \
        == 462_146_578
    assert cfg["job"]["window"] == cfg["max_position_embeddings"]
    assert {k: cfg["job"][k] for k in ("take_batches", "epochs",
                                       "only_normal", "commits")} \
        == {"take_batches": 4, "epochs": 2, "only_normal": False,
            "commits": False}
    assert cfg["job"]["batch_size"] in (1, 2)
    kimi = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-kimi-vl-a3b-instruct.json"))
    assert cfg["guarantees"] == kimi["guarantees"] \
        and cfg["deployment"] == kimi["deployment"]
    assert cfg["normalization"]["ranges"] == kimi["normalization"]["ranges"]
    for key in ("input_output", "router_input", "router_bias",
                "router_weights", "experts", "window", "rotary", "weights",
                "balance_loss", "state", "host_share", "recomputation",
                "checkpoint"):
        assert cfg["assumed"][key]
    assert f"batch_size {cfg['job']['batch_size']}" in cfg["expert_load"]
    assert set(cfg["limits_why"]) >= set(cfg["limits"]["train"])
    for fault in ("window left out", "rotary", "router reading"):
        assert fault in cfg["limits_why"]["how"]


def _required_by_hand(T, W, heads, head):
    """2 per multiply-add of the scores and the weighted sum, a query a
    key inside the mask, summed over a window's positions."""
    met = 0
    for t in range(T):
        for j in range(T):
            met += j <= t and (W is None or j > t - W)
    return 2 * 2 * heads * head * met


def test_the_operations_count_against_a_brute_force_count():
    _, cfg = _config()
    tokens = 4 * 2 * 16384 * 2
    held = 4 * tokens * 6 * 16 / 64   # a balanced router's share, 4 layers
    ops = window_ops.train_ops_bytes(cfg, 16384, tokens, held)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    per_token = {k: v / 3 / tokens for k, v in ops["by_part"].items()}
    # a token, forward, MFLOP (ISSUE 46's table)
    for part, mflop in (("attn_proj", 4 * 41.9), ("attn_global", 117.4),
                        ("attn_window", 3 * 51.4), ("experts", 4 * 17.7),
                        ("router", 4 * 0.33), ("in_out", 0.184)):
        assert per_token[part] == pytest.approx(mflop * 1e6, rel=0.02), part
    assert sum(per_token.values()) == pytest.approx(511e6, rel=2e-3)
    assert window_ops.expert_ops(cfg) == 3 * 2 * 2560 * 768
    assert window_ops.train_ops_bytes(cfg, 16384, tokens, 0)["by_part"][
        "experts"] == 0
    # walked as triangles the three window layers would be 352 for 154
    full = dict(cfg, sliding_window_size=16384)
    assert window_ops.forward_ops_per_token(full, 16384)["attn_window"] \
        == pytest.approx(352.3e6, rel=1e-3)
    # at a tiny shape, position by position
    small = dict(cfg, num_attention_heads=4, head_dim=16,
                 sliding_window_size=24)
    by_part = window_ops.forward_ops_per_token(small, 40)
    assert by_part["attn_global"] * 40 == _required_by_hand(40, None, 4, 16)
    assert by_part["attn_window"] * 40 == 3 * _required_by_hand(40, 24, 4, 16)
    assert window_ops.mask_area(40, 24) == 24 * 25 // 2 + 16 * 24
    assert window_ops.mask_area(40) == window_ops.mask_area(40, 64) == 820
    # the kernels, a call: the band's scores, every key read once
    for kernel, products, streams, stats in (
            ("fwd", 2, 4, 1), ("bwd_dkv", 4, 6, 2), ("bwd_dq", 3, 5, 2)):
        band = window_ops.flash_ops_bytes(kernel, 2, 16384, 28, 128, 4096)
        tri = window_ops.flash_ops_bytes(kernel, 2, 16384, 28, 128)
        assert band["ops"] == products * 2 * 128 * 2 * 28 * 58_722_304
        assert tri["ops"] == products * 2 * 128 * 2 * 28 * 134_225_920
        assert band["bytes"] == tri["bytes"] \
            == streams * 2 * 16384 * 3584 * 4 + stats * 2 * 28 * 16384 * 4


def test_both_readers_and_their_silence_elsewhere(monkeypatch):
    _, cfg = _config()
    tokens = 4 * 2 * 16384 * 2
    held = 4 * tokens * 6 * 16 / 64
    ops = window_ops.train_ops_bytes(cfg, 16384, tokens, held)
    mfu, fill = _reader("train_mfu.window"), _reader("attn_band_fill.train")
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 80.0, mfu.HELD: held * 10},
                        "spans": {"bench.round": (81.0, 10)}, "rounds": 10},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    # 8 s a job of 262,144 tokens
    assert mfu.read(run) == pytest.approx(100 * ops["ops"] / 8.0 / 197e12)
    assert 0 < mfu.read(run) < 100
    # the gauges a fit of this configuration leaves (compiled for a
    # described v5e, PR 46): one causal layer, three band layers
    said = {'iotml_model_layers{kind="attention"}': 1,
            'iotml_model_layers{kind="window_attention"}': 3}
    for kernel in ("fwd", "bwd_dkv", "bwd_dq"):
        for mask, walked, live in (("causal", 142_606_336, 134_225_920),
                                   ("band", 73_400_320, 58_722_304)):
            said[fill.AREA % ("walked", kernel, mask)] = walked
            said[fill.AREA % ("live", kernel, mask)] = live
    monkeypatch.setattr(fill.hs, "registry", lambda: said)
    assert fill.read(run) == pytest.approx(
        100 * (134_225_920 + 3 * 58_722_304)
        / (142_606_336 + 3 * 73_400_320))
    assert 80 < fill.read(run) < 90
    # a window layer walked as a triangle of 1,024² tiles: 41%
    assert 100 * 58_722_304 / 142_606_336 == pytest.approx(41.2, abs=0.1)
    # nothing to read: a program without the gauges (the parent's) or
    # the counter, no chip, no spans, another configuration
    monkeypatch.setattr(fill.hs, "registry", lambda: {
        k: v for k, v in said.items() if "mask" not in k})
    assert fill.read(run) is None
    monkeypatch.setattr(fill.hs, "registry", lambda: said)
    del run.notes["registry"][mfu.HELD]
    assert mfu.read(run) is None
    run.notes["registry"][mfu.HELD] = held
    run.on_chip = lambda: False
    assert mfu.read(run) is None
    run.on_chip, run.notes = (lambda: True), {}
    assert mfu.read(run) is None
    bench = _bench()
    for entry in bench["configs"]:
        if entry["name"] == CONFIG:
            continue
        other = harness.load_json(os.path.join(ROOT, entry["file"]))
        run.cfg = other
        run.notes = {"registry": {key: 80.0, mfu.HELD: held * 10},
                     "spans": {"bench.round": (81.0, 10)}, "rounds": 10}
        assert mfu.read(run) is None and fill.read(run) is None, \
            entry["name"]


def test_the_references_uncut_layer_is_the_sum_of_its_shares():
    """The reference's own functions, handed all sixteen experts of a
    small layer and then four shares of four: every share routes alike
    (on the block's input), and the routed sums add up to the uncut
    layer — no shared expert, so nothing is counted once."""
    import jax
    import jax.numpy as jnp

    entry, cfg = _config()
    mod = harness.load_module(os.path.join(
        ROOT, entry["file"][:-len(".json")] + ".py"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_ffn_hidden_size=24,
               moe_num_primary_experts=16, moe_num_active_primary_experts=3)
    cfg["published"] = dict(cfg["published"], moe_num_primary_experts=16)
    mod.use(cfg)
    p = jax.jit(lambda k: mod._init(k))(jax.random.PRNGKey(5))[
        "layer2"]["moe"]
    rng = np.random.default_rng(5)
    u, h = (jnp.asarray(rng.normal(size=(2, 40, 64)), jnp.float32)
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        want, counts = mod._experts_layer(p, u, h)
        total = jnp.zeros_like(u)
        for first in range(0, 16, 4):
            mod.use(dict(cfg, moe_num_primary_experts=4,
                         experts_held={"first": first}))
            out, again = mod._experts_layer(
                dict(p, experts_in=p["experts_in"][first:first + 4],
                     experts_out=p["experts_out"][first:first + 4]), u, h)
            assert np.array_equal(again, counts)
            total = total + out
    assert int(counts.sum()) == 2 * 40 * 3
    assert float(jnp.abs(total - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 46)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, "\n".join(lines[-25:])
    assert line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert any(ln.startswith("trainer released") for ln in lines)
    # no token dropped: program and reference made the same assignments
    said = next(ln for ln in lines if ln.startswith(
        "assignments to the experts held, first job:"))
    assert "'flipped_share': 0.0" in said and "other_held" in said
    assert any(ln.startswith("check assignment_flip_share: 0.0 <=")
               and ln.endswith("-> ok") for ln in lines)


def test_the_experts_are_placed_on_the_first_jobs_first_batch(monkeypatch):
    """The adapter's trainer reads its stream's first batch through a
    cursor of its own ahead of the weights: they are the rows the first
    job's first step trains on (the driver's recorder kept those, and the
    check regenerated them), the trainer's own cursor has not moved for
    it (the bookkeeping checks hold), and the first job's load on the
    experts held is the balanced one to within an expert's."""
    train = harness.load_module(os.path.join(
        ROOT, "benchmark", "drivers", "train.py"))
    seen, compare = {}, train.compare

    def spy(run, params0, xs, *rest, **kw):
        seen.update(placed_on=run.adapter._STREAM["first_batch"],
                    first=xs[0])
        return compare(run, params0, xs, *rest, **kw)

    monkeypatch.setattr(train, "compare", spy)
    line, lines = _rehearse(0, 4600046)
    assert line["correct"] is True, "\n".join(lines[-25:])
    assert seen["placed_on"].shape == seen["first"].shape == (2, 64, 18)
    assert np.abs(seen["placed_on"] - seen["first"]).max() <= 2e-6
    said = next(ln for ln in lines if ln.startswith(
        "assignments to the experts held a token and layer"))
    first_job = float(said.split("[(")[1].split(",")[0])
    # 3 of 16 a token, 4 held: 0.75 if balanced
    assert abs(first_job - 0.75) <= 0.1, said


def test_traced_rehearsal_reports_the_span_metrics_and_no_device_metric(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 46)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.window divides by a chip's peak and attn_band_fill.train
    # reads the tiles the kernels' grids walk: a rehearsal has no chip
    # and runs the plain attention, and both readers say nothing
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        - set(OWN) >= SPAN_METRICS | SETUP_METRICS
    assert line["metrics"]["recompiles.train"]["value"] == 0


def _not_correct(seed):
    line, lines = _rehearse(0, seed)
    assert line["correct"] is False
    failed = _failed(lines)
    assert failed and set(failed) <= GAPS
    return set(failed)


def test_the_window_left_out_is_not_correct(monkeypatch):
    """The planted fault: the band layers attend to the whole causal
    past."""
    from iotml.models import hybrid

    plain = hybrid.causal_attention
    monkeypatch.setattr(
        hybrid, "causal_attention",
        lambda q, k, v, mode, scale, window=None: plain(q, k, v, mode, scale))
    assert {"update_norm_gap", "update_leaf_gap"} & _not_correct(47)


@pytest.mark.parametrize("turns", [True, False],
                         ids=["in_the_global_layer", "in_no_layer"])
def test_the_rotary_turn_in_the_wrong_layers_is_not_correct(monkeypatch,
                                                            turns):
    """The planted fault: the global layer turns its heads too — or no
    layer does."""
    from iotml.models import hybrid

    monkeypatch.setattr(
        hybrid.HybridConfig, "turns",
        lambda self, layer: turns and self.layer_types[layer]
        in hybrid.GROUPED)
    assert {"update_norm_gap", "update_leaf_gap"} & _not_correct(48)


def test_the_router_on_the_normed_stream_is_not_correct(monkeypatch):
    """The planted fault: the router reads `RMSNorm(h')`, what the
    experts read, in place of the block's own input `h`."""
    from iotml.models import hybrid

    post = hybrid.SensorHybrid.__post_init__

    def swapped(self):
        object.__setattr__(self, "cfg", dataclasses.replace(
            self.cfg, router_input="ffn"))
        post(self)

    monkeypatch.setattr(hybrid.SensorHybrid, "__post_init__", swapped)
    assert {"assignment_flip_share", "update_leaf_gap"} & _not_correct(49)


def test_lower_precision_control_is_not_correct(capsys):
    from benchmark import control

    sets = [a for item in TINY for a in ("--set", item)]
    assert control.main(["--workload", CELL, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    seen = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert len(seen) == 3 and all(s["not_correct_by"] for s in seen)
