"""The cell `ns-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the contract's line,
every per-layer metric the cell lists, the two it brings
(`train_mfu.latent_moe`'s count, `moe_tile_fill.train`), the file against
the source's row, a planted fault caught, and the lower-precision control
coming out as not correct."""

import contextlib
import io
import json
import os
import types

import pytest

from benchmark import harness, latent_moe_ops

ROOT = harness.ROOT
CELL = "ns-train-backlog"
CONFIG = "sensorformer-nemotron-3-super-120b-a12b"
TINY = ["cfg.hidden_size=64", "cfg.mamba_num_heads=4",
        "cfg.mamba_head_dim=8", "cfg.ssm_state_size=8", "cfg.chunk_size=8",
        "cfg.num_attention_heads=2", "cfg.head_dim=16",
        "cfg.moe_latent_size=32", "cfg.moe_intermediate_size=24",
        "cfg.moe_shared_expert_intermediate_size=48",
        "cfg.n_routed_experts=4", "cfg.published.n_routed_experts=16",
        "cfg.num_experts_per_tok=5", "cfg.num_hidden_layers=5",
        'cfg.hybrid_override_pattern="ME*EM"', "cfg.job.window=64"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "mamba_num_heads", "n_groups",
           "num_attention_heads", "num_key_value_heads"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _config():
    entry = next(c for c in _bench()["configs"] if c["name"] == CONFIG)
    return entry, harness.load_json(os.path.join(ROOT, entry["file"]))


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


def test_the_cell_lists_the_ten_shared_metrics_and_its_own_two():
    bench = _bench()
    cell = harness.find_cell(bench, CELL)
    assert cell["config"] == CONFIG
    assert cell["chips"] == 1 and cell["traffic"] == "train_backlog"
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | {"train_mfu.latent_moe",
                                     "moe_tile_fill.train"}
    # found by name, not by place: later PRs append behind them
    own = {m["name"]: (m["source"], m["workloads"])
           for m in bench["per_layer"]
           if m["name"] in ("train_mfu.latent_moe", "moe_tile_fill.train")}
    assert own == {"train_mfu.latent_moe": ("program_span", [CELL]),
                   "moe_tile_fill.train": ("program_counter", [CELL])}
    # what the benchmark had is as it was
    for name, cells in (("train_mfu", ["sf-train-backlog"]),
                        ("train_mfu.hybrid", ["gh-train-backlog"]),
                        ("train_mfu.moe", ["km-train-backlog"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == cells


def test_the_file_holds_the_sources_config_but_for_the_cuts():
    """Every key of the catalog's row under its own name and at its
    published value; `reduced` names the seven that differ — depth, the
    pattern's letters, and the counts of experts, heads and groups held:
    no width — and the file states the published counts and the
    deployment beside them."""
    entry, cfg = _config()
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
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
        assert row["config"]["hybrid_override_pattern"].startswith(
            cfg["hybrid_override_pattern"])
    assert "64 chips share each layer" in cfg["published"]["deployment"]
    assert cfg["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["mamba_num_heads"], cfg["n_groups"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["experts_held"]["first"]) == (11, 8, 16, 1, 4, 1, 0)
    assert (cfg["hidden_size"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"], cfg["head_dim"],
            cfg["moe_latent_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["mlp_hidden_act"], cfg["published"]["n_routed_experts"]) \
        == (4096, 64, 128, 4, 128, 128, 1024, 2688, 5376, 22, 5, "relu2",
            512)
    assert cfg["model"]["parameters"] == latent_moe_ops.parameters(cfg) \
        == 566_799_362
    kimi = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-kimi-vl-a3b-instruct.json"))
    assert cfg["job"] == kimi["job"] and cfg["guarantees"] \
        == kimi["guarantees"] and cfg["deployment"] == kimi["deployment"]
    assert cfg["normalization"]["ranges"] == kimi["normalization"]["ranges"]
    for key in ("multi_token_prediction", "positions", "latent",
                "router_bias", "weights", "input_output", "host_share",
                "recomputation", "checkpoint"):
        assert cfg["assumed"][key]
    assert "352 tokens" in cfg["expert_load"]
    assert set(cfg["limits_why"]) >= set(cfg["limits"]["train"])


def test_the_operations_count_and_both_new_readers():
    _, cfg = _config()
    tokens = 4 * 8192 * 2
    held = 5 * tokens * 22 * 8 / 512   # a balanced router's share, 5 layers
    ops = latent_moe_ops.train_ops_bytes(cfg, 8192, tokens, held)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    per_token = {k: v / 3 / tokens for k, v in ops["by_part"].items()}
    # a token, forward, MFLOP (ISSUE 32's table)
    assert per_token["shared"] == pytest.approx(440.4e6, rel=1e-3)
    assert per_token["latent_proj"] == pytest.approx(83.9e6, rel=1e-3)
    assert per_token["router"] == pytest.approx(21.0e6, rel=2e-3)
    assert per_token["experts"] == pytest.approx(18.9e6, rel=2e-3)
    assert latent_moe_ops.expert_ops(cfg) == 2 * 2 * 1024 * 2688
    assert per_token["ssm_proj"] == pytest.approx(
        5 * 2 * (4096 * 2320 + 1024 * 4096))
    assert per_token["attn"] == pytest.approx(
        4 * 4096 * (512 + 128) + 4 * 512 * 8193 / 2)
    assert sum(per_token.values()) == pytest.approx(724e6, rel=2e-3)
    moe = sum(per_token[k] for k in ("shared", "latent_proj", "router",
                                     "experts"))
    assert 0.75 < moe / sum(per_token.values()) < 0.8
    assert latent_moe_ops.train_ops_bytes(cfg, 8192, tokens, 0)["by_part"][
        "experts"] == 0

    mfu = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_mfu.latent_moe.py"))
    fill = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "moe_tile_fill.train.py"))
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 30.0, mfu.HELD: held * 10,
                                     fill.ROWS % "live": 3000.0,
                                     fill.ROWS % "padding": 9000.0},
                        "spans": {"bench.round": (31.0, 10)}, "rounds": 10},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    # 3 s a job of 65,536 tokens
    assert mfu.read(run) == pytest.approx(100 * ops["ops"] / 3.0 / 197e12)
    assert 0 < mfu.read(run) < 100
    assert fill.read(run) == pytest.approx(25.0)
    # nothing to read: a program without the counters (the parent's), no
    # chip, no spans, another configuration
    del run.notes["registry"][mfu.HELD], \
        run.notes["registry"][fill.ROWS % "padding"]
    assert mfu.read(run) is None and fill.read(run) is None
    run.notes["registry"][mfu.HELD] = held
    run.on_chip = lambda: False
    assert mfu.read(run) is None
    run.on_chip, run.notes = (lambda: True), {}
    assert mfu.read(run) is None and fill.read(run) is None
    run.cfg = {"job": cfg["job"], "model": {"d_model": 1024}}
    assert mfu.read(run) is None


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 32)
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


def test_traced_rehearsal_reports_the_span_metrics_and_the_tiles_fill(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 32)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.latent_moe divides by a chip's peak: a rehearsal has none
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        >= SPAN_METRICS | {"moe_tile_fill.train"}
    assert line["metrics"]["recompiles.train"]["value"] == 0
    assert 0 < line["metrics"]["moe_tile_fill.train"]["value"] <= 100


def test_a_live_tile_left_unwalked_is_not_correct(monkeypatch):
    """The planted fault: every expert layer's walk stops one live tile
    short, forward and backward: the update's worst leaf, the experts'
    own, shows it."""
    from iotml.ops import moe

    plan = moe.dispatch_plan
    monkeypatch.setattr(moe, "dispatch_plan", lambda *a: (
        lambda p: p._replace(live_tiles=p.live_tiles - 1))(plan(*a)))
    line, lines = _rehearse(0, 33)
    assert line["correct"] is False
    failed = [ln.split(":")[0][len("check "):] for ln in lines
              if ln.startswith("check ") and ln.endswith("NOT CORRECT")]
    assert failed and set(failed) <= {"epoch_loss_gap", "moment_norm_gap",
                                      "update_norm_gap", "update_leaf_gap"}
    assert "update_leaf_gap" in failed


def test_lower_precision_control_is_not_correct(capsys):
    from benchmark import control

    sets = [a for item in TINY for a in ("--set", item)]
    assert control.main(["--workload", CELL, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    seen = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert len(seen) == 3 and all(s["not_correct_by"] for s in seen)
