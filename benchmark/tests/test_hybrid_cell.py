"""The cell `gh-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the contract's line,
every per-layer metric the cell lists, the operations `train_mfu.hybrid`
counts, and the lower-precision control coming out as not correct."""

import contextlib
import io
import json
import os
import types

import pytest

from benchmark import harness, hybrid_ops

ROOT = harness.ROOT
CELL = "gh-train-backlog"
TINY = ["cfg.hidden_size=64", "cfg.num_attention_heads=4",
        "cfg.num_key_value_heads=2", "cfg.shared_intermediate_size=128",
        "cfg.mamba_n_heads=4", "cfg.mamba_d_head=16", "cfg.mamba_expand=1",
        "cfg.mamba_d_state=8", "cfg.mamba_chunk_size=8",
        "cfg.num_hidden_layers=3",
        'cfg.layer_types=["mamba", "attention", "mamba"]',
        "cfg.job.window=64"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _config():
    entry = next(c for c in _bench()["configs"]
                 if c["name"] == "sensorformer-granite-4.0-h-micro")
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
    assert cell["chips"] == 1 and cell["traffic"] == "train_backlog"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | {"train_mfu.hybrid"}
    mfu = next(m for m in bench["per_layer"] if m["name"] == "train_mfu")
    assert mfu["workloads"] == ["sf-train-backlog"]


def test_the_file_holds_the_sources_config_but_for_the_depth():
    """Every number of the catalog's row under its own key; `reduced`
    names the one that differs, and one whole period is what is left."""
    entry, cfg = _config()
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == len(cfg["layer_types"])
    held = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert held.count("mamba") == 9 and held.index("attention") == 5
    assert cfg["layer_types"][10:20] == held   # a whole period
    assert (cfg["hidden_size"], cfg["shared_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_chunk_size"]) \
        == (2048, 8192, 32, 8, 64, 64, 128, 4, 256)
    assert cfg["model"]["parameters"] == hybrid_ops.parameters(cfg) \
        == 746_546_130
    assert cfg["job"] == {"batch_size": 1, "take_batches": 4, "epochs": 2,
                          "only_normal": False, "window": 4096,
                          "commits": False}


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 26)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, "\n".join(lines[-25:])
    assert line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert any(ln.startswith("trainer released") for ln in lines)


def test_traced_rehearsal_reports_every_span_metric(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 26)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.hybrid divides by a chip's peak: a rehearsal carries none
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        >= SPAN_METRICS
    assert line["metrics"]["recompiles.train"]["value"] == 0


def test_the_hybrid_mfu_reader_counts_the_cells_operations():
    _, cfg = _config()
    reader = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_mfu.hybrid.py"))
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 30.0},
                        "spans": {"bench.round": (31.0, 10)}},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    tokens = 4 * 4096 * 2
    ops = hybrid_ops.train_ops_bytes(cfg, 4096, tokens)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    assert ops["ops"] / tokens == pytest.approx(4.6156e9, rel=1e-3)
    share = {k: v / ops["ops"] for k, v in ops["by_part"].items()}
    assert share["mlp"] > share["ssm_proj"] > share["attn"] > share["ssd"]
    # 3 s a job of 32,768 tokens
    assert reader.read(run) == pytest.approx(
        100 * ops["ops"] / 3.0 / 197e12)
    assert 0 < reader.read(run) < 100
    # nothing to read: another configuration, no spans, no chip
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
