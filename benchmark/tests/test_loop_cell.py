"""The cell `ou-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the cell's entries
found by name, the file against the source's row, the operations' count
against a hand count and the reader it feeds, the contract's line, every
per-layer metric the cell lists, the passes' losses and exit masses
against the reference's, both planted faults caught, and the
lower-precision control coming out as not correct."""

import contextlib
import io
import json
import os
import types

import pytest

from benchmark import harness, loop_ops

ROOT = harness.ROOT
CELL = "ou-train-backlog"
CONFIG = "sensorformer-ouro-2.6b"
TINY = ["cfg.hidden_size=64", "cfg.num_attention_heads=4",
        "cfg.num_key_value_heads=4", "cfg.head_dim=16",
        "cfg.intermediate_size=96", "cfg.num_hidden_layers=2",
        "cfg.job.window=64"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}
SETUP_METRICS = {"import_s.setup", "backend_s.setup", "state_init_s.setup",
                 "first_fit_s.setup", "trace_lower_s.setup",
                 "compile_s.setup", "cache_misses.setup"}
REDUCED = ["num_hidden_layers", "layer_types"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
GAPS = {"epoch_loss_gap", "moment_norm_gap", "update_norm_gap",
        "update_leaf_gap", "pass_loss_gap", "exit_mass_gap"}


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


def _failed(lines):
    return [ln.split(":")[0][len("check "):] for ln in lines
            if ln.startswith("check ") and ln.endswith("NOT CORRECT")]


def test_the_cells_entries_are_found_by_name():
    """By name, not by place: later PRs append behind them."""
    bench = _bench()
    cell = harness.find_cell(bench, CELL)
    assert cell["config"] == CONFIG
    assert cell["chips"] == 1 and cell["traffic"] == "train_backlog"
    assert len(cell["why"]) <= 200
    entry, _ = _config()
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | {"train_mfu.loop"}
    own = next(m for m in bench["per_layer"] if m["name"] == "train_mfu.loop")
    assert own == {"name": "train_mfu.loop", "unit": "%",
                   "better": "higher", "source": "program_span",
                   "layer": "fit program", "moves": "train_tokens_per_s",
                   "workloads": [CELL]}
    # what the benchmark had is as it was
    for name, cells in (("train_mfu", ["sf-train-backlog"]),
                        ("train_mfu.hybrid", ["gh-train-backlog"]),
                        ("train_mfu.moe", ["km-train-backlog"]),
                        ("train_mfu.latent_moe", ["ns-train-backlog"]),
                        ("moe_tile_fill.train", ["ns-train-backlog"]),
                        ("train_mfu.short_conv", ["lf-train-backlog"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == cells
    assert [w["name"] for w in bench["workloads"]][:5] == [
        "sf-train-backlog", "gh-train-backlog", "km-train-backlog",
        "ns-train-backlog", "lf-train-backlog"]


def test_the_file_holds_the_sources_config_but_for_the_cuts():
    """Every key of the catalog's row under its own name and at its
    published value; `reduced` names the two that differ — depth and the
    layers' kinds: no width, no head count, not the passes — and the
    file states the published values and the deployment beside them."""
    entry, cfg = _config()
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["source_url"] == cfg["source"])
        differ = {k for k, v in row["config"].items() if cfg.get(k, k) != v}
        assert differ == set(REDUCED) and set(row["config"]) <= set(cfg)
        assert cfg["published"] == {k: row["config"][k] for k in REDUCED}
        assert cfg["layer_types"] == row["config"]["layer_types"][:6]
    assert cfg["layer_types"] == ["full_attention"] * 6
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["early_exit_threshold"]) == (6, 4, 1)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["hidden_act"],
            cfg["published"]["num_hidden_layers"]) \
        == (2048, 16, 16, 128, 5632, 1000000, 1e-6, "silu", 48)
    assert cfg["model"]["parameters"] == loop_ops.parameters(cfg) \
        == 308_410_387
    assert cfg["model"]["beta"] == 0.1
    assert cfg["job"] == {"window": 8192, "batch_size": 1, "take_batches": 2,
                          "epochs": 2, "only_normal": False,
                          "commits": False}
    granite = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-granite-4.0-h-micro.json"))
    assert cfg["guarantees"] == granite["guarantees"] \
        and cfg["deployment"] == granite["deployment"]
    assert cfg["normalization"]["ranges"] \
        == granite["normalization"]["ranges"]
    for key in ("shared_parameters", "sandwich_norms", "final_norm",
                "exit_gate", "exit_distribution", "objective", "bias",
                "rotary", "input_output", "weights", "host_share",
                "recomputation", "checkpoint"):
        assert cfg["assumed"][key]
    assert "seven further pipeline stages" in cfg["cut"]["layers"]
    assert "LAST stage" in cfg["cut"]["deployment"]
    assert set(cfg["limits_why"]) >= set(cfg["limits"]["train"])
    assert set(cfg["limits"]["train"]) == GAPS | {"input_row_gap"}


def test_the_operations_count_and_the_reader(monkeypatch):
    _, cfg = _config()
    tokens = 2 * 8192 * 2
    ops = loop_ops.train_ops_bytes(cfg, 8192, tokens, 4)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    # a token and block application, forward, by hand (ISSUE 40):
    # the MLP's three products 3 x 2 x 2,048 x 5,632, the four
    # projections 4 x 2 x 2,048 x 2,048, attention's scores and values
    # over half the window 2 x 2 x 2,048 x 4,096.5
    by_hand = {"mlp": 3 * 2 * 2048 * 5632, "attn_proj": 4 * 2 * 2048 * 2048,
               "attn": 2 * 2 * 2048 * (8192 + 1) / 2}
    assert [round(v / 1e6, 1) for v in by_hand.values()] \
        == [69.2, 33.6, 33.6]
    one = loop_ops.pass_ops_per_token(cfg, 8192)
    for part, value in by_hand.items():
        assert one[part] == 6 * value, part
    assert one["head"] == 2 * 2048 * 18
    per_token = ops["ops"] / 3 / tokens
    assert per_token == pytest.approx(
        24 * sum(by_hand.values()) + 4 * 2 * 2048 * 18 + 2 * 18 * 2048)
    # ISSUE 40 counted eight layers: 4.36 GFLOP forward, 13.09 with the
    # backward; six are three quarters of it
    assert per_token == pytest.approx(0.75 * 4.36e9, rel=2e-3)
    assert ops["ops"] / tokens == pytest.approx(9.816e9, rel=2e-3)
    assert ops["ops"] == pytest.approx(321.6e12, rel=2e-3)   # a job
    # fewer passes, fewer operations: three quarters of the stack's
    three = loop_ops.train_ops_bytes(cfg, 8192, tokens, 3)
    assert three["by_part"]["mlp"] == 0.75 * ops["by_part"]["mlp"]
    assert three["by_part"]["embed"] == ops["by_part"]["embed"]
    # every parameter counted once however often it is applied
    assert loop_ops.parameters(cfg) == 6 * (
        4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048) + 2048 \
        + (18 * 2048 + 2048) + (2048 * 18 + 18) + (2048 + 1)

    mfu = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_mfu.loop.py"))
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 50.0},
                        "spans": {"bench.round": (51.0, 10)}, "rounds": 10},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    # the gauge is the program's: 4 passes, 5 s a job of 32,768 tokens
    said = {mfu.PASSES: 4.0}
    monkeypatch.setattr(mfu.hs, "registry", lambda: said)
    assert mfu.read(run) == pytest.approx(100 * ops["ops"] / 5.0 / 197e12)
    assert 0 < mfu.read(run) < 100
    # a program that ran three passes in the same time reads lower
    said[mfu.PASSES] = 3.0
    assert mfu.read(run) == pytest.approx(
        100 * three["ops"] / 5.0 / 197e12)
    # nothing to read: a program without the gauge (the parent's), no
    # chip, no spans, another configuration
    del said[mfu.PASSES]
    assert mfu.read(run) is None
    said[mfu.PASSES] = 4.0
    run.on_chip = lambda: False
    assert mfu.read(run) is None
    run.on_chip, run.notes = (lambda: True), {}
    assert mfu.read(run) is None
    run.cfg = {"job": cfg["job"], "model": {"d_model": 1024}}
    assert mfu.read(run) is None


def test_the_reader_reads_the_programs_own_gauge():
    """Not patched: `iotml_model_loop_steps` as the program's registry
    holds it after a trace."""
    import jax
    import jax.numpy as jnp

    from iotml.models.hybrid import HybridConfig, SensorHybrid

    mfu = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_mfu.loop.py"))
    jax.eval_shape(SensorHybrid(HybridConfig(loop_steps=3)).init,
                   jax.random.PRNGKey(0), jnp.zeros((1, 8, 18)))
    assert mfu.hs.registry()[mfu.PASSES] == 3


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 40)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, "\n".join(lines[-25:])
    assert line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert any(ln.startswith("trainer released") for ln in lines)
    # the passes' losses and exit masses came out with the losses and
    # are the reference's
    assert any(ln.startswith("passes, reference: first step's loss") for ln in lines)
    assert any(ln.startswith("passes, other side: first step's loss") for ln in lines)
    for name in ("pass_loss_gap", "exit_mass_gap"):
        assert any(ln.startswith(f"check {name}: ")
                   and ln.endswith("-> ok") for ln in lines), name


def test_traced_rehearsal_reports_the_span_metrics_and_no_device_metric(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 40)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.loop divides by a chip's peak: a rehearsal has none, and
    # the reader says nothing
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        >= SPAN_METRICS | SETUP_METRICS
    assert line["metrics"]["recompiles.train"]["value"] == 0


def test_three_passes_instead_of_four_is_not_correct(monkeypatch):
    """The planted fault: the stack run `total_ut_steps − 1` times."""
    from iotml.models import hybrid

    scan = hybrid.nn.scan
    monkeypatch.setattr(
        hybrid.nn, "scan",
        lambda fn, **kw: scan(fn, **dict(kw, length=kw["length"] - 1)))
    line, lines = _rehearse(0, 41)
    assert line["correct"] is False
    failed = _failed(lines)
    assert failed and set(failed) <= GAPS
    # another number of passes cannot be laid beside the reference's
    assert {"pass_loss_gap", "exit_mass_gap"} <= set(failed)
    assert {"update_norm_gap", "update_leaf_gap", "epoch_loss_gap"} \
        & set(failed)


def test_the_output_norms_left_out_is_not_correct(monkeypatch):
    """The planted fault: a block adds its parts' outputs as they come,
    `h + Attn(N1(h))`, `a + Mlp(N3(a))` (the norms' weights stay in the
    tree, applied to nothing)."""
    from iotml.models import hybrid

    normed = hybrid.HybridBlock._post_norm
    monkeypatch.setattr(
        hybrid.HybridBlock, "_post_norm",
        lambda self, part, which: (normed(self, part, which), part)[1])
    line, lines = _rehearse(0, 42)
    assert line["correct"] is False
    failed = _failed(lines)
    assert failed and set(failed) <= GAPS
    assert {"update_norm_gap", "update_leaf_gap", "epoch_loss_gap",
            "pass_loss_gap"} & set(failed)


def test_lower_precision_control_is_not_correct(capsys):
    from benchmark import control

    sets = [a for item in TINY for a in ("--set", item)]
    assert control.main(["--workload", CELL, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    seen = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert len(seen) == 3 and all(s["not_correct_by"] for s in seen)
