"""The cell `km-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the contract's line,
every per-layer metric the cell lists, the operations `train_mfu.moe`
counts, the file against the source's row, and the lower-precision
control coming out as not correct."""

import contextlib
import io
import json
import os
import types

import pytest

from benchmark import harness, moe_ops

ROOT = harness.ROOT
CELL = "km-train-backlog"
CONFIG = "sensorformer-kimi-vl-a3b-instruct"
TINY = ["cfg.hidden_size=64", "cfg.num_attention_heads=4",
        "cfg.intermediate_size=96", "cfg.moe_intermediate_size=24",
        "cfg.kv_lora_rank=32", "cfg.qk_nope_head_dim=16",
        "cfg.qk_rope_head_dim=8", "cfg.v_head_dim=16",
        "cfg.num_hidden_layers=3", "cfg.n_routed_experts=4",
        "cfg.published.n_routed_experts=16", "cfg.num_experts_per_tok=3",
        "cfg.n_shared_experts=1", "cfg.job.window=64"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}
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


def test_the_cell_lists_eleven_layer_metrics_and_its_own_mfu():
    bench = _bench()
    cell = harness.find_cell(bench, CELL)
    assert cell["config"] == CONFIG
    assert cell["chips"] == 1 and cell["traffic"] == "train_backlog"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | {"train_mfu.moe"}
    # by name: later cells append their entries after this one's
    for name, cells in (("train_mfu.moe", [CELL]),
                        ("train_mfu", ["sf-train-backlog"]),
                        ("train_mfu.hybrid", ["gh-train-backlog"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == cells


def test_the_file_holds_the_sources_config_but_for_the_two_cuts():
    """Every key of the catalog's row under its own name and at its
    published value; `reduced` names the two that differ, and the file
    states the published counts and the deployment beside them."""
    entry, cfg = _config()
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers", "n_routed_experts"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["source_url"] == cfg["source"])
        differ = [k for k, v in row["config"].items() if cfg.get(k, k) != v]
        assert differ == cfg["reduced"] and set(row["config"]) <= set(cfg)
    assert cfg["published"]["num_hidden_layers"] == 27
    assert cfg["published"]["n_routed_experts"] == 64
    assert "eight chips share each layer" in cfg["published"]["deployment"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["experts_held"]["first"]) == (6, 8, 0)
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["first_k_dense_replace"], cfg["routed_scaling_factor"]) \
        == (2048, 11264, 1408, 16, 512, 128, 64, 128, 6, 2, 1, 2.446)
    assert cfg["model"]["parameters"] == moe_ops.parameters(cfg) \
        == 585_080_146
    assert cfg["job"] == {"batch_size": 1, "take_batches": 4, "epochs": 2,
                          "only_normal": False, "window": 8192,
                          "commits": False}
    assert "expert_placement" not in cfg["assumed"]   # 0-7 as they come
    for key in ("router_bias", "rotary", "vision",
                "input_output", "host_share", "recomputation", "checkpoint"):
        assert cfg["assumed"][key]
    assert "768 tokens" in cfg["expert_load"]
    granite = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-granite-4.0-h-micro.json"))
    assert cfg["guarantees"] == granite["guarantees"]
    assert set(cfg["limits_why"]) >= set(cfg["limits"]["train"])


def test_the_files_ranges_centre_the_fleets_records():
    """`normalization.ranges` are calibrated to the fleet: a normalised
    field has mean 0 and deviation 0.5 over the benchmark's own cars, so
    no vector common to every record leads the stream (the reference's
    hand-picked ranges leave 63% of a record's energy in one), and the
    adapter's Normalizer is the program's own over the same ranges."""
    import importlib.util

    import numpy as np

    from benchmark import fleet

    _, cfg = _config()
    ranges = cfg["normalization"]["ranges"]
    raw = fleet.Fleet(11, 50_000, cfg["assumed"]["failure_rate"]).step()[0]
    rows = fleet.normalize(raw, ranges)
    on = [r is not None for r in ranges]
    assert sum(on) == 14 and not rows[:, [not o for o in on]].any()
    assert np.abs(rows.mean(axis=0)).max() < 0.05
    assert np.abs(rows[:, on].std(axis=0) - 0.5).max() < 0.02
    common = lambda r: float(  # noqa: E731
        (r.mean(axis=0) ** 2).sum() / (r ** 2).sum(axis=1).mean())
    granite = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-granite-4.0-h-micro.json"))
    assert common(rows) < 0.01 < 0.5 < common(
        fleet.normalize(raw, granite["normalization"]["ranges"]))
    spec = importlib.util.spec_from_file_location(
        "bench_kimi_adapter", os.path.join(ROOT, "benchmark", "configs",
                                           CONFIG + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    np.testing.assert_allclose(mod.normalizer(cfg).np(raw), rows,
                               rtol=0, atol=2e-6)


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 30)
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
    # and that is a check of the run, beside the norms
    assert any(ln.startswith("check assignment_flip_share: 0.0 <=")
               and ln.endswith("-> ok") for ln in lines)


def test_traced_rehearsal_reports_every_span_metric(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 30)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.moe divides by a chip's peak: a rehearsal carries none
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        >= SPAN_METRICS
    assert line["metrics"]["recompiles.train"]["value"] == 0


def test_the_moe_mfu_reader_counts_the_cells_operations():
    _, cfg = _config()
    reader = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_mfu.moe.py"))
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    tokens = 4 * 8192 * 2
    held = 5 * tokens * 0.75      # a balanced router's share, five layers
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 30.0, reader.HELD: held * 10},
                        "spans": {"bench.round": (31.0, 10)}, "rounds": 10},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    ops = moe_ops.train_ops_bytes(cfg, 8192, tokens, held)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    per_token = {k: v / 3 / tokens for k, v in ops["by_part"].items()}
    # a token and expert layer, forward, MFLOP (ISSUE 30's table)
    assert per_token["attn"] / 6 == pytest.approx(41.9e6, rel=2e-3)
    assert per_token["attn_proj"] / 6 == pytest.approx(27.5e6, rel=2e-3)
    assert per_token["shared"] / 5 == pytest.approx(34.6e6, rel=2e-3)
    assert per_token["experts"] / 5 == pytest.approx(13.0e6, rel=3e-3)
    assert per_token["dense_mlp"] == pytest.approx(138.4e6, rel=2e-3)
    assert ops["ops"] / tokens == pytest.approx(2.38e9, rel=1e-2)
    assert moe_ops.train_ops_bytes(cfg, 8192, tokens, 0)["by_part"][
        "experts"] == 0
    # 3 s a job of 65,536 tokens
    assert reader.read(run) == pytest.approx(
        100 * ops["ops"] / 3.0 / 197e12)
    assert 0 < reader.read(run) < 100
    # the kernels' counts: the causal half, both widths
    fwd = moe_ops.flash_ops_bytes("fwd", 1, 8192, 16, 192, 128)
    assert fwd["ops"] == pytest.approx(8192 * per_token["attn"] / 6)
    assert moe_ops.flash_ops_bytes("bwd_dkv", 1, 8192, 16, 192, 128)[
        "ops"] == pytest.approx(2 * fwd["ops"])
    tiles = moe_ops.expert_tiles_ops_bytes(cfg, 8192, 16)
    assert tiles["ops"] == 8192 * 3 * 2 * 2048 * 1408
    # nothing to read: a program without the counter (the parent's), no
    # spans, no chip, another configuration
    del run.notes["registry"][reader.HELD]
    assert reader.read(run) is None
    run.notes["registry"][reader.HELD] = held
    run.on_chip = lambda: False
    assert reader.read(run) is None
    run.on_chip, run.notes = (lambda: True), {}
    assert reader.read(run) is None
    run.cfg = {"job": cfg["job"], "model": {"d_model": 1024}}
    assert reader.read(run) is None


def test_lower_precision_control_is_not_correct(capsys):
    from benchmark import control

    sets = [a for item in TINY for a in ("--set", item)]
    assert control.main(["--workload", CELL, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    seen = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert len(seen) == 3 and all(s["not_correct_by"] for s in seen)
