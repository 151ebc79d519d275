"""The cell `lf-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the cell's entries
found by name, the file against the source's row, the operations' count
and the reader it feeds, the contract's line, every per-layer metric the
cell lists, the reference's uncut expert layer against its shares, both
planted faults caught, and the lower-precision control coming out as not
correct."""

import contextlib
import io
import json
import os
import types

import pytest

from benchmark import harness, short_conv_ops

ROOT = harness.ROOT
CELL = "lf-train-backlog"
CONFIG = "sensorformer-lfm2-24b-a2b"
TINY = ["cfg.hidden_size=64", "cfg.num_attention_heads=4",
        "cfg.num_key_value_heads=2", "cfg.intermediate_size=96",
        "cfg.moe_intermediate_size=24", "cfg.num_experts=4",
        "cfg.published.num_experts=16", "cfg.num_experts_per_tok=3",
        "cfg.job.window=64"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}
SETUP_METRICS = {"import_s.setup", "backend_s.setup", "state_init_s.setup",
                 "first_fit_s.setup", "trace_lower_s.setup",
                 "compile_s.setup", "cache_misses.setup"}
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts"]
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


def _failed(lines):
    return [ln.split(":")[0][len("check "):] for ln in lines
            if ln.startswith("check ") and ln.endswith("NOT CORRECT")]


def test_the_cells_entries_are_found_by_name():
    """By name, not by place: later PRs append behind them (PERF.md §7
    records why a test that indexed `per_layer[-1]` broke)."""
    bench = _bench()
    cell = harness.find_cell(bench, CELL)
    assert cell["config"] == CONFIG
    assert cell["chips"] == 1 and cell["traffic"] == "train_backlog"
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | {"train_mfu.short_conv"}
    own = next(m for m in bench["per_layer"]
               if m["name"] == "train_mfu.short_conv")
    assert own == {"name": "train_mfu.short_conv", "unit": "%",
                   "better": "higher", "source": "program_span",
                   "layer": "fit program", "moves": "train_tokens_per_s",
                   "workloads": [CELL]}
    # what the benchmark had is as it was
    for name, cells in (("train_mfu", ["sf-train-backlog"]),
                        ("train_mfu.hybrid", ["gh-train-backlog"]),
                        ("train_mfu.moe", ["km-train-backlog"]),
                        ("train_mfu.latent_moe", ["ns-train-backlog"]),
                        ("moe_tile_fill.train", ["ns-train-backlog"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == cells
    assert [w["name"] for w in bench["workloads"]][:4] == [
        "sf-train-backlog", "gh-train-backlog", "km-train-backlog",
        "ns-train-backlog"]


def test_the_file_holds_the_sources_config_but_for_the_cuts():
    """Every key of the catalog's row under its own name and at its
    published value; `reduced` names the four that differ — depth, the
    layers' kinds, the leading dense layers counted once, the experts
    held: no width and no head count — and the file states the
    published values and the deployment beside them."""
    entry, cfg = _config()
    assert entry["source"] == cfg["source"] and cfg["source"].endswith(
        "LiquidAI/LFM2-24B-A2B/blob/main/config.json")
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
        # published layers 0 and 2-5
        kinds = row["config"]["layer_types"]
        assert cfg["layer_types"] == [kinds[0]] + kinds[2:6]
    assert "eight chips share each layer" in cfg["published"]["deployment"]
    assert cfg["layer_types"] == ["conv", "full_attention"] + ["conv"] * 3
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["experts_held"]["first"]) == (5, 1, 8, 0)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["conv_bias"],
            cfg["rope_parameters"]["rope_theta"],
            cfg["routed_scaling_factor"], cfg["norm_eps"],
            cfg["published"]["num_experts"]) \
        == (2048, 32, 8, 11776, 1536, 4, 3, False, 1000000, 1, 1e-5, 64)
    assert cfg["model"]["parameters"] == short_conv_ops.parameters(cfg) \
        == 452_583_826
    assert cfg["job"] == {"window": 8192, "batch_size": 2, "take_batches": 4,
                          "epochs": 2, "only_normal": False,
                          "commits": False}
    kimi = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-kimi-vl-a3b-instruct.json"))
    assert cfg["guarantees"] == kimi["guarantees"] \
        and cfg["deployment"] == kimi["deployment"]
    assert cfg["normalization"]["ranges"] == kimi["normalization"]["ranges"]
    for key in ("input_output", "taps", "rotary", "qk_norm",
                "router_weights", "router_bias", "weights", "state",
                "host_share", "recomputation", "checkpoint"):
        assert cfg["assumed"][key]
    assert "1,024 tokens an expert" in cfg["expert_load"]
    assert set(cfg["limits_why"]) >= set(cfg["limits"]["train"])


def test_the_operations_count_and_the_reader():
    _, cfg = _config()
    tokens = 4 * 2 * 8192 * 2
    held = 4 * tokens * 4 * 8 / 64   # a balanced router's share, 4 layers
    ops = short_conv_ops.train_ops_bytes(cfg, 8192, tokens, held)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    per_token = {k: v / 3 / tokens for k, v in ops["by_part"].items()}
    # a token, forward, MFLOP (ISSUE 38's table)
    for part, mflop in (("conv_proj", 134.2), ("short_conv", 0.066),
                        ("attn_proj", 21.0), ("attn", 33.6),
                        ("dense_mlp", 144.7), ("router", 1.05),
                        ("in_out", 0.15), ("experts", 37.7)):
        assert per_token[part] == pytest.approx(mflop * 1e6, rel=0.02), part
    assert per_token["short_conv"] == 4 * (2 * 3 + 2) * 2048
    assert short_conv_ops.expert_ops(cfg) == 3 * 2 * 2048 * 1536
    assert sum(per_token.values()) == pytest.approx(372.5e6, rel=1e-3)
    assert ops["ops"] == pytest.approx(146.5e12, rel=1e-3)
    assert short_conv_ops.train_ops_bytes(cfg, 8192, tokens, 0)["by_part"][
        "experts"] == 0
    # the gates fused into the kernels: four streams a forward for eight
    moved = short_conv_ops.short_conv_bytes(cfg, 16384)
    assert moved["stream"] == 16384 * 2048 * 4 == 134_217_728
    assert moved["built"]["fwd"] == 2 * moved["fused"]["fwd"] == 8 * 2 ** 27
    assert short_conv_ops.conv_ops_bytes("fwd", 2, 8192, 2048, 3) == {
        "ops": 6 * 2 ** 25, "bytes": 8 * 2 ** 25}
    assert short_conv_ops.conv_ops_bytes("bwd", 2, 8192, 2048, 3) == {
        "ops": 12 * 2 ** 25, "bytes": 12 * 2 ** 25}

    mfu = harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "train_mfu.short_conv.py"))
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 30.0, mfu.HELD: held * 10},
                        "spans": {"bench.round": (31.0, 10)}, "rounds": 10},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    # 3 s a job of 131,072 tokens
    assert mfu.read(run) == pytest.approx(100 * ops["ops"] / 3.0 / 197e12)
    assert 0 < mfu.read(run) < 100
    # nothing to read: a program without the counter (the parent's), no
    # chip, no spans, another configuration
    del run.notes["registry"][mfu.HELD]
    assert mfu.read(run) is None
    run.notes["registry"][mfu.HELD] = held
    run.on_chip = lambda: False
    assert mfu.read(run) is None
    run.on_chip, run.notes = (lambda: True), {}
    assert mfu.read(run) is None
    run.cfg = {"job": cfg["job"], "model": {"d_model": 1024}}
    assert mfu.read(run) is None


def test_the_references_uncut_layer_is_the_sum_of_its_shares():
    """The reference's own functions, handed all sixteen experts of a
    small layer and then eight shares of two: every share routes alike,
    and the routed sums add up to the uncut layer — no shared expert,
    so nothing is counted once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    entry, cfg = _config()
    mod = harness.load_module(os.path.join(
        ROOT, entry["file"][:-len(".json")] + ".py"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, moe_intermediate_size=24,
               num_experts=16, num_experts_per_tok=3)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    mod.use(cfg)
    p = jax.jit(lambda k: mod._init(k))(jax.random.PRNGKey(5))[
        "layer2"]["moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, counts = mod._experts_layer(p, u)
        total = jnp.zeros_like(u)
        for first in range(0, 16, 2):
            mod.use(dict(cfg, num_experts=2,
                         experts_held={"first": first}))
            out, again = mod._experts_layer(
                dict(p, experts_in=p["experts_in"][first:first + 2],
                     experts_out=p["experts_out"][first:first + 2]), u)
            assert np.array_equal(again, counts)
            total = total + out
    assert int(counts.sum()) == 2 * 40 * 3
    assert float(jnp.abs(total - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 38)
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


def test_traced_rehearsal_reports_the_span_metrics_and_no_device_metric(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 38)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.short_conv divides by a chip's peak: a rehearsal has
    # none, and the reader says nothing
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        >= SPAN_METRICS | SETUP_METRICS
    assert line["metrics"]["recompiles.train"]["value"] == 0


def test_the_oldest_tap_left_out_is_not_correct(monkeypatch):
    """The planted fault: every short convolution without the tap that
    meets the position two back, forward and backward."""
    from iotml.models import hybrid

    fused = hybrid.causal_conv1d_fused
    monkeypatch.setattr(
        hybrid, "causal_conv1d_fused",
        lambda x, kernel, *a, **kw: fused(x, kernel.at[0].set(0.0), *a, **kw))
    line, lines = _rehearse(0, 39)
    assert line["correct"] is False
    failed = _failed(lines)
    # another model sends some tokens to other experts, too
    assert failed and set(failed) <= {"epoch_loss_gap", "moment_norm_gap",
                                      "update_norm_gap", "update_leaf_gap",
                                      "assignment_flip_share"}
    assert {"update_norm_gap", "update_leaf_gap"} & set(failed)


def test_the_rotary_left_off_k_is_not_correct(monkeypatch):
    """The planted fault: the attention layer's queries turned, its keys
    (the operand of two heads at the tiny preset) left as they are."""
    from iotml.models import hybrid
    from iotml.ops import moe

    turn = moe.rotary
    monkeypatch.setattr(
        hybrid.moe, "rotary",
        lambda x, theta: x if x.shape[2] == 2 else turn(x, theta))
    line, lines = _rehearse(0, 40)
    assert line["correct"] is False
    failed = _failed(lines)
    # another model sends some tokens to other experts, too
    assert failed and set(failed) <= {"epoch_loss_gap", "moment_norm_gap",
                                      "update_norm_gap", "update_leaf_gap",
                                      "assignment_flip_share"}
    assert {"update_norm_gap", "update_leaf_gap"} & set(failed)


def test_lower_precision_control_is_not_correct(capsys):
    from benchmark import control

    sets = [a for item in TINY for a in ("--set", item)]
    assert control.main(["--workload", CELL, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    seen = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert len(seen) == 3 and all(s["not_correct_by"] for s in seen)
