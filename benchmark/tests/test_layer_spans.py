"""The per-layer metrics that read the program's phase spans and compile
counters (`iotml.obs.tracing.phase`, `iotml_compile_seconds`): a traced
rehearsal of `sf-train-backlog` on the CPU reports each, they agree
with the metrics that time the same work from outside, and each entry
has its reader."""

import json
import os

import pytest

from benchmark import harness

ROOT = harness.ROOT
OLD = {"batching_ms.train", "fit_ms.train", "round_rest_ms.train"}
NEW = {"fetch_ms.train", "stack_ms.train", "transfer_ms.train",
       "dispatch_ms.train", "sync_ms.train", "fit_max_ms.train",
       "recompiles.train"}
SF_TINY = ["log_scale=0.05", "cfg.model.d_model=64",
           "cfg.model.num_heads=2", "cfg.model.num_layers=2",
           "cfg.model.max_len=64", "cfg.job.window=64"]


@pytest.fixture(scope="module")
def traced():
    """One traced rehearsal, read by every test of this file."""
    import contextlib
    import io

    import benchmark.run as bench_run

    argv = ["--workload", "sf-train-backlog", "--seed", "11", "--seconds",
            "3", "--trace", "1"]
    for item in SF_TINY:
        argv += ["--set", item]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert bench_run.main(argv) == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


def test_the_traced_rehearsal_reports_every_span_metric(
        traced, rehearsed_layer_metrics):
    line, lines = traced
    assert line["correct"] is True, "\n".join(lines[-25:])
    # train_mfu is a device metric: a rehearsal carries none; what
    # later PRs listed for every cell (the set-up's spans) is the
    # cell's too
    assert set(line["metrics"]) == rehearsed_layer_metrics(
        harness.load_json(os.path.join(ROOT, "BENCHMARK.json")),
        "sf-train-backlog") >= OLD | NEW
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["recompiles.train"] == 0
    # the same stacking, timed from inside and by subtraction (what is
    # between them is the span's own cost under the profiler: 0.50-0.77
    # ms a round on this sandbox's CPU at PR 42, on either tree)
    assert abs(value["stack_ms.train"]
               - value["round_rest_ms.train"]) < 1.0
    # the fit's three parts are the fit
    parts = value["transfer_ms.train"] + value["dispatch_ms.train"] \
        + value["sync_ms.train"]
    assert parts <= value["fit_ms.train"]
    assert parts == pytest.approx(value["fit_ms.train"], rel=0.02)
    # the consumer's calls are part of the batching
    assert 0 < value["fetch_ms.train"] <= value["batching_ms.train"]
    # the slowest job is at least the mean job
    assert value["fit_max_ms.train"] >= value["fit_ms.train"]
    assert any(ln.startswith("slowest job: round ") and "; sync " in ln
               for ln in lines)


def test_decode_is_fused_into_the_fetch_on_the_native_wire(traced):
    """`decode_ms.train` has a reader and no entry in BENCHMARK.json:
    over the native wire client the decode is part of the fetch call,
    the reader finds nothing, and a listed metric has to be reported."""
    line, _ = traced
    assert "decode_ms.train" not in line["metrics"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "decode_ms.train.py"))


def test_readers_find_nothing_in_a_program_without_spans():
    """What the parent commit gives them: no such series, no `phases`."""

    class Parent:
        notes = {"registry": {
            'iotml_step_seconds_sum{loop="train",phase="host_pipeline"}':
                1.0}, "spans": {"bench.round": (2.0, 2)}, "rounds": 2}

    for name in sorted(NEW - {"fit_max_ms.train"}) + ["decode_ms.train"]:
        reader = harness.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert reader.read(Parent) is None, name


def test_every_new_entry_has_its_reader_and_its_layer():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert NEW <= set(entries)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] in OLD
              } | {"log and wire"}  # PERF.md section 3's list
    for name in NEW:
        m = entries[name]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert m["moves"] == "train_tokens_per_s" and "workloads" not in m
        assert m["layer"] in layers
        assert m["source"] == ("program_counter" if name.startswith(
            "recompiles") else "program_span")
    # appended after what was there, in no other place
    assert [m["name"] for m in bench["per_layer"]][:4] == [
        "batching_ms.train", "fit_ms.train", "round_rest_ms.train",
        "train_mfu"]
