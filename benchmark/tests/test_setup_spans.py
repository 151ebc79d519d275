"""The per-layer metrics under `setup_s` (PR 34): they read the
program's `iotml.start.*` spans and its compile counters by program; a
traced rehearsal of `sf-train-backlog` on the CPU reports each, the
story line `set-up by span:` lays them over `setup_s`, and a program
without the spans (the parent's) gives the readers nothing and does not
make them raise.  Entries of `BENCHMARK.json` are found by name."""

import json
import math
import os
import re

import pytest

from benchmark import harness

ROOT = harness.ROOT
SPANS = {"import_s.setup": "entry point", "backend_s.setup": "entry point",
         "state_init_s.setup": "train loop",
         "first_fit_s.setup": "train loop"}
COUNTERS = {"trace_lower_s.setup": "fit program",
            "compile_s.setup": "fit program",
            "cache_misses.setup": "fit program"}
SETUP = {**SPANS, **COUNTERS}
SF_TINY = ["log_scale=0.05", "cfg.model.d_model=64",
           "cfg.model.num_heads=2", "cfg.model.num_layers=2",
           "cfg.model.max_len=64", "cfg.job.window=64"]


def _reader(name: str):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


@pytest.fixture(scope="module")
def traced():
    """One traced rehearsal, read by every test of this file."""
    import contextlib
    import io

    import benchmark.run as bench_run

    argv = ["--workload", "sf-train-backlog", "--seed", "3400000011",
            "--seconds", "3", "--trace", "1"]
    for item in SF_TINY:
        argv += ["--set", item]
    # the counters are the process's totals: what tests that ran in it
    # before this file compiled is taken off where programs are compared
    traced_before = dict(
        _reader("trace_lower_s.setup").compile_seconds(("trace", "lower")),
        misses=_reader("cache_misses.setup").read(None))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert bench_run.main(argv) == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines, traced_before


def test_the_traced_rehearsal_reports_all_seven_with_finite_values(traced):
    line, lines, _ = traced
    assert line["correct"] is True, "\n".join(lines[-25:])
    for name in SETUP:
        assert name in line["metrics"], name
        entry = line["metrics"][name]
        assert math.isfinite(entry["value"]) and entry["value"] >= 0, name
        assert entry["unit"] == ("count" if name.startswith("cache_")
                                 else "s")
    # what the benchmark had is reported as before
    for name in ("batching_ms.train", "fit_ms.train", "fetch_ms.train",
                 "sync_ms.train", "fit_max_ms.train", "recompiles.train"):
        assert name in line["metrics"], name


def test_the_first_fit_holds_the_tracing_of_its_program(traced):
    line, _, before = traced
    value = {k: v["value"] for k, v in line["metrics"].items()}
    by = _reader("trace_lower_s.setup").compile_seconds(("trace", "lower"))
    value["cache_misses.setup"] -= before.pop("misses")
    value["trace_lower_s.setup"] -= sum(before.values())
    by = {k: v - before.get(k, 0.0) for k, v in by.items()}
    # the fit's program is traced and lowered inside the first fit's
    # dispatch; `iotml_state_init` is the adapter's `seed_weights`, ahead
    assert 0 < by["iotml_scanned_fit"] <= value["first_fit_s.setup"]
    assert value["trace_lower_s.setup"] == pytest.approx(sum(by.values()))
    assert value["trace_lower_s.setup"] >= by["iotml_scanned_fit"]
    # a process pinned to the CPU keeps no cache: every program is built,
    # no lookup is made, so none misses
    assert value["compile_s.setup"] > 0
    assert value["cache_misses.setup"] == 0.0
    assert value["backend_s.setup"] > 0 and value["state_init_s.setup"] > 0
    # (the package's own import is the first span of all, in a process
    # that had not imported it before this file ran)
    assert value["import_s.setup"] >= 0


def test_the_story_line_lays_the_spans_over_setup_s(traced):
    _, lines, _ = traced
    (story,) = [ln for ln in lines if ln.startswith("set-up by span: ")]
    setup_s = float(re.search(r"setup_s ([\d.]+);", story).group(1))
    covered = float(re.search(r"spans cover ([\d.]+) s", story).group(1))
    assert 0 < covered <= setup_s
    for word in ("backend ", "state_init ", "import ", "first fit ",
                 "dispatch ", "slowest imports: ", "bare stretches"):
        assert word in story, story
    # the spans are laid over the lap lines' clock: the fit ends before
    # the window opens
    laps = [ln for ln in lines if ln.startswith("set-up +")]
    last = float(re.match(r"set-up \+ *([\d.]+) s", laps[-1]).group(1))
    assert last <= setup_s


def test_the_readers_find_nothing_in_a_program_without_the_spans(
        monkeypatch):
    """What the parent commit gives them: no finder, no `program` label
    on the cache's counter; nothing raises."""
    from iotml.obs import metrics, tracing

    class Parent:
        notes = {"registry": {}, "spans": {}, "rounds": 2}

    monkeypatch.delattr(tracing, "time_imports")
    monkeypatch.setitem(metrics.DECLARED_METRIC_LABELS, "compile_cache",
                        ("result",))
    for name in ("import_s.setup", "backend_s.setup", "state_init_s.setup",
                 "cache_misses.setup"):
        assert _reader(name).read(Parent) is None, name
    # a program with no ring at all
    monkeypatch.delattr(tracing, "phases")
    assert _reader("first_fit_s.setup").read(Parent) is None


def test_a_program_with_the_spans_reports_zero_where_nothing_was_spent():
    from iotml.obs import tracing

    tracing.reset()
    assert _reader("backend_s.setup").read(None) == 0.0
    assert _reader("state_init_s.setup").read(None) == 0.0
    assert _reader("import_s.setup").read(None) == 0.0


def test_every_entry_has_its_reader_its_layer_and_moves_setup_s():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(SETUP) <= set(entries)
    for name, layer in SETUP.items():
        m = entries[name]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert m["moves"] == "setup_s" and "workloads" not in m
        assert m["better"] == "lower" and m["layer"] == layer
        assert m["source"] == ("program_span" if name in SPANS
                               else "program_counter")
    # every cell reports setup_s, so every cell reports these
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup
    # appended after what was there
    names = [m["name"] for m in bench["per_layer"]]
    assert min(names.index(n) for n in SETUP) > names.index(
        "moe_tile_fill.train")
