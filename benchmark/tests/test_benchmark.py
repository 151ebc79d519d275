"""What the benchmark's files promise, checked on the CPU at tiny sizes:
the trace reduction against a recorded trace, the fleet's determinism
and independence, a rehearsal of each traffic driver down to the result
line, the control coming out as not correct, and a broken timed path
coming out as not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import fleet, harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = harness.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# sizes a test run can hold: a 50,000-record log, 10,000 records/s live
# (slower, and one 50,000-row drain outlasts the test)
SF_TINY = ["cfg.model.d_model=64", "cfg.model.num_heads=2",
           "cfg.model.num_layers=2", "cfg.model.max_len=64",
           "cfg.job.window=64"]
TINY = {"sf-train-backlog": ["log_scale=0.05"] + SF_TINY,
        "ae-train-backlog": ["log_scale=0.05"],
        "lstm-train-backlog": ["log_scale=0.05"],
        "ae-score-backlog": ["log_scale=0.05"],
        "ae-score-live": ["rate_per_s=10000"]}


def bench_of(workload):
    """BENCHMARK.json, or for a cell the memory floor keeps out of it
    the file that keeps it runnable by hand."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if harness.find_cell(bench, workload) is None:
        bench = harness.load_json(os.path.join(ROOT, "benchmark",
                                               "unlisted.json"))
    return bench


def rehearse(capsys, workload, trace=0, seed=7, seconds=2):
    import benchmark.run as bench_run

    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    for item in TINY[workload]:
        argv += ["--set", item]
    assert bench_run.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


# ------------------------------------------------------- trace reduction
def test_trace_reduction_of_a_recorded_trace():
    # recorded on a TPU v5 lite: 20 steps of one jitted 256x256 product,
    # each a copy-start, a copy-done and a fusion, 5 ms of host sleep apart
    planes = trace_reduce.read_planes(
        os.path.join(HERE, "data", "probe.xplane.pb"))
    ops = [e for name, lines in planes if name == "/device:TPU:0"
           for _ln, evs in lines for e in evs]
    assert len(ops) == 60
    out = trace_reduce.reduce(planes)
    # the operations of this trace do not overlap: the union is their sum
    assert out["busy_s"] == pytest.approx(sum(e[2] for e in ops) / 1e9)
    assert 0 < out["busy_s"] < 1e-4 < out["window_s"]
    assert out["device_ops"][0][0].startswith("%fusion")
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.host"] == pytest.approx(20 * 0.005, rel=0.15)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(
        out["window_s"])


def test_trace_reduction_attributes_gaps_to_the_innermost_span():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [("XLA Ops", [("%a", 10 * ms, 10 * ms),
                                        ("%b", 15 * ms, 10 * ms),
                                        ("%c", 60 * ms, 5 * ms)])]),
        ("/host:CPU", [("python3", [
            ("bench.window", 0, 100 * ms),
            ("bench.round", 5 * ms, 70 * ms),
            ("bench.publish", 30 * ms, 20 * ms)])])]
    out = trace_reduce.reduce(planes)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.020)  # a and b overlap
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.publish"] == pytest.approx(0.020)
    # 5..10, 25..30, 50..60 and 65..75 lie in the round and in no child
    assert gaps["bench.round"] == pytest.approx(0.030)
    assert gaps[trace_reduce.OUTSIDE] == pytest.approx(0.030)
    with pytest.raises(ValueError):
        trace_reduce.reduce(planes[1:])


# ---------------------------------------------------------------- fleet
def test_fleet_is_seeded_and_decodes_with_the_programs_codec():
    def records(seed):
        raw, failing, car = fleet.Fleet(seed, 500).step()
        return raw, failing, car, fleet.encode(raw, failing)

    raw, failing, car, msgs = records(2**31 + 11)
    again = records(2**31 + 11)
    assert msgs == again[3] and np.array_equal(car, again[2])
    assert msgs != records(12)[3]
    from iotml.core.schema import KSQL_CAR_SCHEMA
    from iotml.ops.avro import AvroCodec

    codec = AvroCodec(KSQL_CAR_SCHEMA)
    for i in (0, 499, int(np.argmax(failing))):
        rec = codec.decode(msgs[i][5:])
        got = [rec[f.name] for f in KSQL_CAR_SCHEMA.sensor_fields]
        assert np.allclose(got, raw[i], rtol=0, atol=0)
        assert rec["FAILURE_OCCURRED"] == ("true" if failing[i] else "false")


def test_fleet_imports_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); from benchmark import fleet;"
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('iotml', 'jax')]; assert not bad, bad" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True)


def test_burst_warp_keeps_the_mean_rate():
    from benchmark import logchild

    t = np.linspace(0, 20, 200_001)
    w = logchild.warp(t, {"every_s": 5, "for_s": 1, "factor": 3})
    assert np.all(np.diff(w) >= 0) and w[-1] == pytest.approx(20.0)
    in_burst = ((w % 5) < 1).mean()
    assert in_burst == pytest.approx(3 / 7, abs=1e-3)


# ------------------------------------------------------------ rehearsals
@pytest.mark.parametrize("workload", sorted(TINY))
def test_rehearsal_ends_in_the_contracts_line(capsys, workload):
    line, lines = rehearse(capsys, workload)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True, "\n".join(lines[-25:])
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    bench = bench_of(workload)
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("workload", ["sf-train-backlog",
                                      "ae-train-backlog"])
def test_traced_rehearsal_reports_the_cells_layer_metrics(capsys, workload):
    line, _ = rehearse(capsys, workload, trace=1)
    assert set(line) == RESULT_KEYS  # no device: no breakdown, no busy_s
    assert set(line["metrics"]) == {"batching_ms.train", "fit_ms.train",
                                    "round_rest_ms.train"}


def test_no_device_named_and_none_found_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "sf-train-backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert got.returncode != 0
    assert not any(ln.startswith("{") for ln in got.stdout.splitlines())


# ------------------------------------------- the control, the broken path
@pytest.mark.parametrize("workload", ["sf-train-backlog",
                                      "ae-train-backlog",
                                      "lstm-train-backlog",
                                      "ae-score-backlog"])
def test_lower_precision_control_is_not_correct(capsys, workload):
    from benchmark import control

    sets = [a for item in TINY[workload] if item.startswith("cfg.")
            for a in ("--set", item)]
    assert control.main(["--workload", workload, "--seeds", "3,4,5",
                         "--cars", "20000"] + sets) == 0
    for ln in capsys.readouterr().out.splitlines():
        if ln.startswith("{"):
            assert json.loads(ln)["not_correct_by"]


def test_a_fit_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    from iotml.ops import fused_train

    real = fused_train.fused_fit

    def lazy(state, xs, masks, epochs, **kw):
        _state, losses, accs = real(state, xs, masks, epochs, **kw)
        return state, losses, accs

    monkeypatch.setattr(fused_train, "fused_fit", lazy)
    line, lines = rehearse(capsys, "ae-train-backlog")
    assert line["correct"] is False
    assert any("update_norm_gap" in ln and "NOT CORRECT" in ln
               for ln in lines)


def test_a_scanned_fit_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from iotml.train import loop

    real = loop.Trainer.fit_compiled

    def lazy(self, batches, **kw):
        before = jax.tree.map(jnp.copy, self.state)  # the fit donates it
        history = real(self, batches, **kw)
        self.state = before
        return history

    monkeypatch.setattr(loop.Trainer, "fit_compiled", lazy)
    line, lines = rehearse(capsys, "sf-train-backlog")
    assert line["correct"] is False
    assert any("update_norm_gap" in ln and "NOT CORRECT" in ln
               for ln in lines)


def test_an_altered_prediction_is_not_correct(capsys, monkeypatch):
    from iotml.serve import scorer

    real = scorer.format_rows
    monkeypatch.setattr(scorer, "format_rows",
                        lambda rows: real(rows + np.float32(0.01)))
    line, lines = rehearse(capsys, "ae-score-backlog")
    assert line["correct"] is False
    assert any("mean_abs_gap" in ln and "NOT CORRECT" in ln for ln in lines)


@pytest.mark.parametrize("workload", ["sf-train-backlog",
                                      "lstm-train-backlog"])
def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch,
                                                   workload):
    from iotml.train import loop

    real = loop.Trainer.fit_compiled

    class Half:
        def __init__(self, inner):
            self.inner = inner

        def epochs(self, n):
            for it in self.inner.epochs(n):
                yield (b for i, b in enumerate(it) if i % 2 == 0)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    monkeypatch.setattr(loop.Trainer, "fit_compiled",
                        lambda self, batches, **kw: real(
                            self, Half(batches), **kw))
    line, lines = rehearse(capsys, workload)
    assert line["correct"] is False
    # sliding windows a record apart: the batches' losses hardly differ,
    # and the steps left out show in the parameters' change instead
    number = "update_norm_gap" if workload.startswith("sf-") \
        else "epoch_loss_gap"
    assert any(number in ln and "NOT CORRECT" in ln for ln in lines)


# ----------------------------------------------------------- the layout
@pytest.mark.parametrize("listed", ["BENCHMARK.json",
                                    "benchmark/unlisted.json"])
def test_every_named_thing_is_a_file_of_its_own(listed):
    bench = harness.load_json(os.path.join(ROOT, listed))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(ROOT, c["file"][:-5] + ".py"))
        assert harness.load_json(os.path.join(ROOT, c["file"]))[
            "reduced"] == c["reduced"]
    for w in bench["workloads"]:
        traffic = harness.load_json(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", traffic["driver"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    from benchmark import kernels

    assert kernels.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        kernels.peaks("TPU v9 imaginary")
