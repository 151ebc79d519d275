"""What the benchmark's files promise, checked on the CPU at tiny sizes:
the trace reduction against a recorded trace, the fleet's determinism
and independence, a rehearsal of each traffic driver down to the result
line, the control coming out as not correct, and a broken timed path
coming out as not correct."""

import json
import os
import random
import subprocess
import sys
import types

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


# ---------------------------------------------------- the trace's start
RUN_S, TRACE_S = 40.0, 3.0
T0 = 86_400.25  # the window opens at some instant of the host's clock


def old_due(now, t_end, length_s, _longest_s):
    """The rule before PR 42, kept here so that the tests say what they
    guard: the window's last `trace_seconds`, whatever a job's length."""
    return now >= t_end - length_s


def calls_of(first_s, period_s, jitter=None):
    """The instants of the window at which a driver's loop asks whether
    the trace is due: as it opens, then at each job's end inside it."""
    out, t, k = [0.0], first_s, 0
    while t < RUN_S:
        out.append(t)
        k += 1
        t = first_s + k * period_s if jitter is None \
            else t + period_s * jitter.uniform(0.98, 1.02)
    return out


def trace_start(calls, tmp_path):
    """Walk `calls` through TraceWindow's own bookkeeping: (the index of
    the call the trace starts at, the TraceWindow), None where none."""
    tw = harness.TraceWindow(bare_run(tmp_path), TRACE_S)
    for i, at in enumerate(calls):
        if tw.due(T0 + at, T0 + RUN_S):
            return i, tw
    return None, tw


def bare_run(tmp_path, platform="tpu", trace=True):
    return types.SimpleNamespace(
        seconds=RUN_S, trace=trace, path=lambda n: str(tmp_path / n),
        on_chip=lambda: platform != "cpu",
        spans=types.SimpleNamespace(annotate=None))


@pytest.mark.parametrize("excess,phase,jittered", [
    (0.0, 0.0, False), (0.03, 0.0, False), (0.10, 0.0, False),
    (0.0, 0.37, False), (0.0, 0.81, False), (0.10, 0.50, False),
    (0.0, 0.0, True)], ids=[
    "even", "first_3pc", "first_10pc", "mid_job_37pc", "mid_job_81pc",
    "first_10pc_mid_job_50pc", "jitter_2pc"])
def test_a_trace_always_starts_whatever_the_jobs_length(
        tmp_path, excess, phase, jittered):
    """Job periods 0.2 to 13 s (`run_seconds` / 3) in steps of 0.01, a
    first job up to 10% longer than the rest, a window that opens at
    any phase of a job, periods that vary by 2%: the trace starts
    inside the window, at a boundary from which a whole job is traced,
    and no earlier than one longest stretch and `trace_seconds` before
    the end."""
    for n in range(20, 1301):
        period = n / 100
        jitter = random.Random(n) if jittered else None
        calls = calls_of(period * (1 + excess) * (1 - phase), period, jitter)
        i, tw = trace_start(calls, tmp_path)
        assert i is not None, (period, calls[-3:], tw.longest_s)
        assert calls[i] < RUN_S
        assert calls[i] >= RUN_S - (tw.longest_s + TRACE_S), period
        # where every job is shorter than trace_seconds (and the
        # margin), the instant is the old rule's, call for call
        if tw.longest_s * harness.TRACE_MARGIN <= TRACE_S:
            assert calls[i - 1] - 1e-6 < RUN_S - TRACE_S <= calls[i] + 1e-6


def test_the_old_rule_took_no_trace_where_no_boundary_fell_in_its_last_3_s(
        tmp_path, monkeypatch):
    """What PR 42 mends, named: with `now >= t_end - trace_seconds` a
    cell whose job outlasts `trace_seconds` has a trace only where a
    boundary happens to fall in the window's last 3 s."""
    monkeypatch.setattr(harness, "trace_due", old_due)
    missed = [n / 100 for n in range(20, 1301)
              if trace_start(calls_of(n / 100, n / 100), tmp_path)[0]
              is None]
    assert missed and min(missed) > TRACE_S
    # no k with 37 <= k * p < 40: the zones ISSUE 42 lists under 4 s,
    # (3.077, 3.083), (3.333, 3.364) and (3.636, 3.700)
    assert [p for p in missed if p < 4 and p not in (3.36, 3.7)] == [
        3.08, 3.34, 3.35, 3.64, 3.65, 3.66, 3.67, 3.68, 3.69]


# (first job, the rest) in seconds of the window.  `ou` on the accepted
# tree (ledger, PR 40: fit 3,863.5 + batching 12.4 + rest 0.7 ms; the
# first job fetches in the foreground) and under PR 41's change (its
# builder's traced runs: `rounds: 3.6574 / 3.6584 / 3.7827 s`)
OU_ACCEPTED, OU_PR41 = (3.99, 3.877), (3.7827, 3.6584)


def test_ou_on_the_accepted_tree_starts_where_the_old_rule_started(
        tmp_path, monkeypatch):
    calls = calls_of(*OU_ACCEPTED)
    i, tw = trace_start(calls, tmp_path)
    assert i == 10 and calls[i] == pytest.approx(38.883)
    assert tw.longest_s == pytest.approx(3.99)  # due from 35.81 s
    assert calls[9] < RUN_S - harness.TRACE_MARGIN * 3.99 <= calls[10]
    monkeypatch.setattr(harness, "trace_due", old_due)
    assert trace_start(calls, tmp_path)[0] == 10


def test_pr41s_boundaries_start_a_trace_that_the_old_rule_never_started(
        tmp_path, monkeypatch):
    calls = calls_of(*OU_PR41)
    i, tw = trace_start(calls, tmp_path)
    # the tenth boundary, 0.29 s short of the old rule's 37 s; the job
    # traced ends at 40.37 s and closes the window
    assert i == 10 and calls[i] == pytest.approx(36.7083)
    assert calls[i] + OU_PR41[1] == pytest.approx(40.3667)
    monkeypatch.setattr(harness, "trace_due", old_due)
    assert trace_start(calls, tmp_path)[0] is None


@pytest.mark.parametrize("cell,first_ms,rest_ms", [
    # ledger, PR 40: the larger `fit_max_ms.train` of the pair as the
    # first job, `fit_ms` + `batching_ms` + `round_rest_ms` as the rest
    ("sf", 906.97, 676.62), ("gh", 1913.8, 1854.55),
    ("km", 2472.1, 2268.8), ("ns", 2144.9, 1949.64),
    ("lf", 2690.4, 2415.63)])
def test_cells_whose_jobs_are_shorter_start_at_the_old_rules_call(
        tmp_path, monkeypatch, cell, first_ms, rest_ms):
    calls = calls_of(first_ms / 1e3, rest_ms / 1e3)
    new, tw = trace_start(calls, tmp_path)
    assert tw.longest_s * harness.TRACE_MARGIN < TRACE_S
    monkeypatch.setattr(harness, "trace_due", old_due)
    assert new == trace_start(calls, tmp_path)[0] is not None


def test_a_traced_run_on_a_chip_that_took_no_trace_says_so(tmp_path):
    tw = harness.TraceWindow(bare_run(tmp_path), TRACE_S)
    for at in (0.0, 3.78, 7.44):  # then nothing: the loop died early
        assert not tw.due(T0 + at, T0 + RUN_S)
    with pytest.raises(SystemExit) as stopped:
        tw.stop()
    said = str(stopped.value)
    assert stopped.value.code != 0 and "never started" in said
    assert "asked 3 times" in said and "0.000, 3.780, 7.440 s" in said
    assert "stretch between two calls was 3.780 s" in said
    assert "due from 36.031 s" in said  # 40 - 1.05 * 3.78
    # a rehearsal on the CPU has no trace to report, and an untraced
    # run asked for none
    assert harness.TraceWindow(bare_run(tmp_path, "cpu"), TRACE_S).stop() \
        == {}
    assert harness.TraceWindow(bare_run(tmp_path, trace=False),
                               TRACE_S).stop() == {}


def test_a_trace_without_a_device_plane_says_so_on_a_chip(tmp_path):
    """The CPU's profiler writes no device plane: told it is a chip,
    the run ends with the reduction's reason and the trace's start."""
    import jax.numpy as jnp

    tw = harness.TraceWindow(bare_run(tmp_path), TRACE_S)
    tw.maybe_start(T0 + 38.5, T0 + RUN_S)
    assert tw.t0 is not None
    jnp.ones((8, 8)).sum().block_until_ready()
    tw.close_window()
    with pytest.raises(SystemExit) as stopped:
        tw.stop()
    said = str(stopped.value)
    assert "no device plane" in said and "started at 38.500 s" in said
    assert not os.path.exists(tw.dir)


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
def test_traced_rehearsal_reports_the_cells_layer_metrics(
        capsys, workload, rehearsed_layer_metrics):
    line, _ = rehearse(capsys, workload, trace=1)
    assert set(line) == RESULT_KEYS  # no device: no breakdown, no busy_s
    assert set(line["metrics"]) == rehearsed_layer_metrics(
        bench_of(workload), workload)


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
