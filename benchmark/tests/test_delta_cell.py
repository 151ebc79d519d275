"""The cell `kl-train-backlog` rehearsed on the CPU at a tiny preset
(`--set` overrides of the configuration's widths): the cell's entries
found by name, the file against the source's row, the operations' count
against a brute-force count at a tiny shape and the reader it feeds
(silent for every other configuration and for a program without the
gauges), the contract's line, every per-layer metric the cell lists, the
reference's uncut expert layer against its shares, the experts placed on
the first job's first batch, the three planted faults caught, and the
lower-precision control coming out as not correct."""

import contextlib
import dataclasses
import io
import json
import os
import types

import numpy as np
import planted_faults
import pytest

from benchmark import delta_ops, harness

ROOT = harness.ROOT
CELL = "kl-train-backlog"
CONFIG = "sensorformer-kimi-linear-48b-a3b"
TINY = ["cfg.hidden_size=64", "cfg.num_attention_heads=4",
        "cfg.intermediate_size=96", "cfg.moe_intermediate_size=24",
        "cfg.kv_lora_rank=32", "cfg.qk_nope_head_dim=16",
        "cfg.qk_rope_head_dim=8", "cfg.v_head_dim=16", "cfg.num_experts=4",
        "cfg.published.num_experts=16", "cfg.num_experts_per_token=3",
        "cfg.kda_chunk_size=16",
        "cfg.linear_attn_config.head_dim=16",
        "cfg.linear_attn_config.num_heads=4", "cfg.job.window=64",
        "cfg.job.batch_size=2"]
SPAN_METRICS = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train",
                "fetch_ms.train", "stack_ms.train", "transfer_ms.train",
                "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
                "recompiles.train"}
SETUP_METRICS = {"import_s.setup", "backend_s.setup", "state_init_s.setup",
                 "first_fit_s.setup", "trace_lower_s.setup",
                 "compile_s.setup", "cache_misses.setup"}
OWN = "train_mfu.delta"
REDUCED = ["num_hidden_layers", "num_experts", "linear_attn_config"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
GAPS = {"epoch_loss_gap", "moment_norm_gap", "update_norm_gap",
        "update_leaf_gap", "assignment_flip_share"}


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _config():
    entry = next(c for c in _bench()["configs"] if c["name"] == CONFIG)
    return entry, harness.load_json(os.path.join(ROOT, entry["file"]))


def _reader():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", OWN + ".py"))


def _rehearse(trace: int, seed: int, also=()):
    import benchmark.run as bench_run

    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--set", "log_scale=0.05"]
    for item in TINY + list(also):
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
    assert len(cell["why"]) <= 200 and "32x" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and m["moves"] == "train_tokens_per_s"}
    assert listed == SPAN_METRICS | {OWN}
    assert next(m for m in bench["per_layer"] if m["name"] == OWN) == {
        "name": OWN, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "fit program",
        "moves": "train_tokens_per_s", "workloads": [CELL]}
    # what the benchmark had is as it was
    for name, cells in (("train_mfu", ["sf-train-backlog"]),
                        ("train_mfu.hybrid", ["gh-train-backlog"]),
                        ("train_mfu.moe", ["km-train-backlog"]),
                        ("train_mfu.latent_moe", ["ns-train-backlog"]),
                        ("moe_tile_fill.train", ["ns-train-backlog"]),
                        ("train_mfu.short_conv", ["lf-train-backlog"]),
                        ("train_mfu.loop", ["ou-train-backlog"]),
                        ("train_mfu.window", ["st-train-backlog"]),
                        ("attn_band_fill.train", ["st-train-backlog"])):
        assert next(m for m in bench["per_layer"]
                    if m["name"] == name)["workloads"] == cells
    assert [w["name"] for w in bench["workloads"]][:7] == [
        "sf-train-backlog", "gh-train-backlog", "km-train-backlog",
        "ns-train-backlog", "lf-train-backlog", "ou-train-backlog",
        "st-train-backlog"]


def test_the_file_holds_the_sources_config_but_for_the_cuts():
    """Every key of the catalog's row under its own name and at its
    published value; `reduced` names the three that differ — depth, the
    experts held, the group whose two layer lists are cut to the layers
    held: no width and no head count — and the file states the published
    values and the deployment beside them."""
    entry, cfg = _config()
    # the catalog's `source_url` itself, as the driver looks a row up; the
    # cut is in the entry's `why`
    assert entry["source"] == cfg["source"] \
        and "layers 1-5, 8 of 256 experts" in entry["why"] \
        and len(entry["why"]) <= 200
    assert cfg["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["source_url"] == cfg["source"])
        differ = {k for k, v in row["config"].items() if cfg.get(k, k) != v}
        assert differ == set(REDUCED) and set(row["config"]) <= set(cfg)
        lin, was = cfg["linear_attn_config"], \
            row["config"]["linear_attn_config"]
        # inside the group only the two lists differ: no width
        assert {k for k in was if lin[k] != was[k]} \
            == {"kda_layers", "full_attn_layers"}
        assert cfg["published"]["linear_attn_config"] == {
            k: was[k] for k in ("full_attn_layers", "kda_layers")}
        assert (cfg["published"]["num_hidden_layers"],
                cfg["published"]["num_experts"]) == (27, 256)
    assert "32 chips share each layer" in cfg["published"]["deployment"]
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5], "num_heads": 32,
        "short_conv_kernel_size": 4}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["experts_held"]["first"]) == (5, 8, 0)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_token"], cfg["num_shared_experts"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["mla_use_nope"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"]) \
        == (2304, 32, 9216, 1024, 8, 1, 512, 128, 64, 128, True, 2.446,
            1e-5)
    assert cfg["model"]["parameters"] == delta_ops.parameters(cfg) \
        == 508_147_858
    assert cfg["kda_chunk_size"] == 64 and "kda_gate_rank" not in cfg
    assert cfg["job"]["window"] == 16384
    assert {k: cfg["job"][k] for k in ("epochs", "only_normal", "commits")} \
        == {"epochs": 2, "only_normal": False, "commits": False}
    assert cfg["job"]["batch_size"] in (1, 2) \
        and cfg["job"]["take_batches"] in (2, 4)
    kimi = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs",
        "sensorformer-kimi-vl-a3b-instruct.json"))
    assert cfg["guarantees"] == kimi["guarantees"] \
        and cfg["deployment"] == kimi["deployment"]
    assert cfg["normalization"]["ranges"] == kimi["normalization"]["ranges"]
    for key in ("input_output", "kda_gate_rank", "kda_gate_init",
                "kda_convolution", "kda_norms", "kda_chunk", "kda_state",
                "mla", "router", "weights", "balance_loss", "host_share",
                "recomputation", "checkpoint"):
        assert cfg["assumed"][key]
    assert f"batch_size {cfg['job']['batch_size']}" in cfg["expert_load"] \
        and f"take_batches {cfg['job']['take_batches']}" \
        in cfg["expert_load"]
    assert set(cfg["limits_why"]) >= set(cfg["limits"]["train"])
    for fault in ("averaged over a head's", "delta correction dropped",
                  "rotary turn"):
        assert fault in cfg["limits_why"]["how"]


def _chunk_by_hand(C, K, V):
    """2 per multiply-add, entry by entry: the two score matrices'
    causal halves, T by forward substitution (row r reads the rows above
    it, row s of which has s + 1 entries), W and U by T's triangle, the
    three products with the state, the scores' product with Ṽ."""
    ops = 0
    for r in range(C):
        for s in range(r):
            ops += 2 * K            # A[r, s]
            ops += 2 * (s + 1)      # T[r, :] −= A[r, s] · T[s, :]
        for s in range(r + 1):
            ops += 2 * K            # the queries' scores
            ops += 2 * (K + V)      # W[r] and U[r] += T[r, s] · (k_s, v_s)
            ops += 2 * V            # O[r] += scores[r, s] · Ṽ[s]
        ops += 3 * 2 * K * V        # W S, (Q ⊙ e^G) S, the state's update
    return ops


def test_the_operations_count_against_a_brute_force_count():
    _, cfg = _config()
    job = cfg["job"]
    tokens = job["take_batches"] * job["batch_size"] * 16384 * 2
    held = 4 * tokens * 8 * 8 / 256   # a balanced router's share, 4 layers
    ops = delta_ops.train_ops_bytes(cfg, 16384, tokens, held)
    assert ops["ops"] == pytest.approx(sum(ops["by_part"].values()))
    per_token = {k: v / 3 / tokens for k, v in ops["by_part"].items()}
    # a token, forward, MFLOP (ISSUE 50's table; the scan by this file's
    # count, which takes the inverse by substitution)
    for part, mflop in (("kda_proj", 315.7), ("kda_conv", 0.393),
                        ("mla_proj", 58.2), ("mla_attn", 167.8),
                        ("dense_mlp", 127.4), ("shared", 56.6),
                        ("router", 4.72), ("experts", 14.2),
                        ("in_out", 0.166), ("kda_scan", 18.0)):
        assert per_token[part] == pytest.approx(mflop * 1e6, rel=0.01), part
    assert sum(per_token.values()) == pytest.approx(763e6, rel=2e-3)
    assert delta_ops.expert_ops(cfg) == 3 * 2 * 2304 * 1024
    assert delta_ops.train_ops_bytes(cfg, 16384, tokens, 0)["by_part"][
        "experts"] == 0
    # the chunk, entry by entry, at a tiny shape and at the cell's
    for C, K, V in ((4, 3, 2), (16, 8, 6), (64, 128, 128)):
        assert delta_ops.kda_chunk_ops(C, K, V) \
            == pytest.approx(_chunk_by_hand(C, K, V), rel=0.02), (C, K, V)
    # the chunk size is the program's to say, and the layers: half the
    # chunk is fewer operations, no KDA layer none
    by_chunk = [delta_ops.forward_ops_per_token(cfg, 16384, c)["kda_scan"]
                for c in (32, 64, 128)]
    assert by_chunk == sorted(by_chunk) and by_chunk[0] < 0.9 * by_chunk[1]
    assert delta_ops.forward_ops_per_token(cfg, 16384, 64, 0)["kda_scan"] \
        == 0
    # one call: a window's chunks, its operands and results once
    fwd = delta_ops.kda_ops_bytes(1, 16384, 32, 128, 128, 64)
    bwd = delta_ops.kda_ops_bytes(1, 16384, 32, 128, 128, 64, "bwd")
    assert fwd["ops"] == 32 * 256 * delta_ops.kda_chunk_ops(64, 128, 128)
    assert bwd["ops"] == 2 * fwd["ops"]
    assert fwd["bytes"] == 16384 * 32 * 4 * (5 * 128 + 1)
    assert bwd["bytes"] == 16384 * 32 * 4 * (9 * 128 + 2)


def test_the_reader_and_its_silence_elsewhere(monkeypatch):
    _, cfg = _config()
    job = cfg["job"]
    tokens = job["take_batches"] * job["batch_size"] * 16384 * 2
    held = 4 * tokens * 8 * 8 / 256
    mfu = _reader()
    key = 'iotml_step_seconds_sum{loop="train",phase="device_compute"}'
    run = types.SimpleNamespace(
        cfg=cfg, notes={"registry": {key: 80.0, mfu.HELD: held * 10},
                        "spans": {"bench.round": (81.0, 10)}, "rounds": 10},
        device={"platform": "tpu", "device_kind": "TPU v5 lite"},
        on_chip=lambda: True)
    said = {mfu.CHUNK: 64, mfu.LAYERS: 4}
    monkeypatch.setattr(mfu.hs, "registry", lambda: said)
    ops = delta_ops.train_ops_bytes(cfg, 16384, tokens, held, 64, 4)
    # 8 s a job
    assert mfu.read(run) == pytest.approx(100 * ops["ops"] / 8.0 / 197e12)
    assert 0 < mfu.read(run) < 100
    # a program that chunked otherwise, or ran fewer such layers, is
    # counted by what it says
    monkeypatch.setattr(mfu.hs, "registry",
                        lambda: {mfu.CHUNK: 32, mfu.LAYERS: 3})
    assert mfu.read(run) < 100 * ops["ops"] / 8.0 / 197e12
    # nothing to read: a program without the gauges (the parent's) or
    # the counter, no chip, no spans, another configuration
    for gone in (mfu.CHUNK, mfu.LAYERS):
        monkeypatch.setattr(mfu.hs, "registry", lambda gone=gone: {
            k: v for k, v in said.items() if k != gone})
        assert mfu.read(run) is None
    monkeypatch.setattr(mfu.hs, "registry", lambda: said)
    del run.notes["registry"][mfu.HELD]
    assert mfu.read(run) is None
    run.notes["registry"][mfu.HELD] = held
    run.on_chip = lambda: False
    assert mfu.read(run) is None
    run.on_chip, run.notes = (lambda: True), {}
    assert mfu.read(run) is None
    for entry in _bench()["configs"]:
        if entry["name"] == CONFIG:
            continue
        run.cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
        run.notes = {"registry": {key: 80.0, mfu.HELD: held * 10},
                     "spans": {"bench.round": (81.0, 10)}, "rounds": 10}
        assert mfu.read(run) is None, entry["name"]


def test_the_references_uncut_layer_is_the_sum_of_its_shares():
    """The reference's own functions, handed all sixteen experts of a
    small layer and then four shares of four: every share routes alike,
    and the four outputs, the shared expert counted ONCE, add up to the
    uncut layer."""
    import jax
    import jax.numpy as jnp

    entry, cfg = _config()
    mod = harness.load_module(os.path.join(
        ROOT, entry["file"][:-len(".json")] + ".py"))
    cfg.update(hidden_size=64, moe_intermediate_size=24, num_experts=16,
               num_experts_per_token=3)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    mod.use(cfg)
    km = mod._km
    p = {"router": 0.02 * jax.random.normal(jax.random.PRNGKey(5), (64, 16)),
         "router_bias": jnp.zeros((16,)),
         "experts_in": 0.02 * jax.random.normal(jax.random.PRNGKey(6),
                                                (16, 64, 48)),
         "experts_out": 0.02 * jax.random.normal(jax.random.PRNGKey(7),
                                                 (16, 24, 64)),
         "shared_in": {"kernel": 0.02 * jax.random.normal(
             jax.random.PRNGKey(8), (64, 48))},
         "shared_out": {"kernel": 0.02 * jax.random.normal(
             jax.random.PRNGKey(9), (24, 64))}}
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, counts = km._experts_layer(p, u)
        shared = km._gated(u, p["shared_in"]["kernel"],
                           p["shared_out"]["kernel"])
        total = jnp.zeros_like(u)
        for first in range(0, 16, 4):
            mod.use(dict(cfg, num_experts=4, experts_held={"first": first}))
            out, again = km._experts_layer(
                dict(p, experts_in=p["experts_in"][first:first + 4],
                     experts_out=p["experts_out"][first:first + 4]), u)
            assert np.array_equal(again, counts)
            total = total + out
    assert int(counts.sum()) == 2 * 40 * 3
    assert float(jnp.abs(total - 3 * shared - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())


def test_rehearsal_ends_in_the_contracts_line():
    line, lines = _rehearse(0, 2**31 + 50)
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
    # the experts were placed on the first batch: 3 of 16 a token, 4
    # held, 0.75 a token and layer if balanced
    said = next(ln for ln in lines if ln.startswith(
        "assignments to the experts held a token and layer"))
    assert abs(float(said.split("[(")[1].split(",")[0]) - 0.75) <= 0.1, said


def test_traced_rehearsal_reports_the_span_metrics_and_no_device_metric(
        rehearsed_layer_metrics):
    line, lines = _rehearse(1, 50)
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu.delta divides by a chip's peak: a rehearsal has none
    assert set(line["metrics"]) == rehearsed_layer_metrics(_bench(), CELL) \
        - {OWN} >= SPAN_METRICS | SETUP_METRICS
    assert line["metrics"]["recompiles.train"]["value"] == 0


def _not_correct(seed):
    """The numbers that refuse a rehearsal under a TENTH of the file's
    limits: the CPU multiplies in full float32, where a sound rehearsal
    reads under a thousandth of each (the chip's products round their
    operands to bfloat16, and the file's limits stand over that)."""
    limits = _config()[1]["limits"]["train"]
    line, lines = _rehearse(0, seed, [
        f"cfg.limits.train.{k}={v / 10}" for k, v in limits.items()
        if k in GAPS])
    assert line["correct"] is False
    failed = _failed(lines)
    assert failed and set(failed) <= GAPS
    return set(failed)


@pytest.mark.parametrize("fault,seed", [
    ("scalar_gate", 51), ("no_delta", 52), ("rope", 53)])
def test_a_planted_fault_is_not_correct(monkeypatch, fault, seed):
    """`planted_faults.py`'s three, each in the program alone: the decay
    averaged over a head's channels, the delta correction dropped, the
    rotary turn in the latent layer."""
    planted_faults.plant(fault, monkeypatch.setattr)
    assert {"update_norm_gap", "update_leaf_gap"} & _not_correct(seed)


def test_lower_precision_control_is_not_correct(capsys):
    from benchmark import control

    sets = [a for item in TINY for a in ("--set", item)]
    assert control.main(["--workload", CELL, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    seen = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert len(seen) == 3 and all(s["not_correct_by"] for s in seen)
