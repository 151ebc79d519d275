"""The control of `correct`: the plain reference computed in the nearest
precision below the one the configuration states (bfloat16 parameters,
inputs, outputs and optimizer moments), put in the program's place and
compared by the same function against the same limits.  It has to come
out as not correct.  No benchmark run runs it; by hand, on the chip, at
the cell's own size:

    python3 benchmark/control.py --workload ae-train-backlog --seeds 11,12,13

prints one JSON line per seed with the control's numbers beside the
limits, and exits 1 if any seed's control would pass as correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def control_numbers(run, cars: int) -> dict:
    """The control's numbers for this run's cell, on the head of the
    seeded stream at the job's own size."""
    import jax.numpy as jnp

    from benchmark import fleet
    from benchmark import harness as hs

    job = run.cfg["job"]
    f = fleet.Fleet(run.seed, cars, run.cfg["assumed"]["failure_rate"],
                    run.cfg["deployment"]["interval_s"])
    raw, failing, _ = f.step()
    rows = fleet.normalize(raw, run.cfg["normalization"]["ranges"])
    b, s = job["batch_size"], job["take_batches"]
    driver = hs.load_module(os.path.join(
        hs.BENCH, "drivers", run.traffic["driver"] + ".py"))
    if run.traffic["driver"] == "score":
        weights = driver.served_weights(run, cars)
        sample = rows[:run.traffic["check_batches"] * b]
        return driver.compare(run, weights, sample, None,
                              dtype=jnp.bfloat16)
    if job.get("only_normal"):
        rows = rows[~failing]
    window = job.get("window")
    s = min(s, (len(rows) - (window or 0)) // b)
    if window:
        # window i holds rows i .. i+window-1, its target is row i+window
        wins = np.lib.stride_tricks.sliding_window_view(
            rows, window, axis=0)[:s * b].transpose(0, 2, 1)
        xs = np.ascontiguousarray(wins).reshape(s, b, window, 18)
        ys = rows[window:s * b + window].reshape(s, b, 1, 18)
    else:
        xs = ys = rows[:s * b].reshape(s, b, 18)
    masks = np.ones((s, b), np.float32)
    params0 = run.adapter.init_params(run.seed)
    return driver.compare(run, params0, xs, ys, masks, None, None,
                          dtype=jnp.bfloat16)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cars", type=int, default=None)
    ap.add_argument("--set", action="append", default=[], metavar="K=V")
    args = ap.parse_args(argv)

    from benchmark import harness as hs
    from iotml.utils.device import claim_device

    device = claim_device()
    bench = hs.load_json(os.path.join(hs.ROOT, "BENCHMARK.json"))
    if hs.find_cell(bench, args.workload) is None:
        bench = hs.load_json(os.path.join(hs.BENCH, "unlisted.json"))
    cell = hs.find_cell(bench, args.workload)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        run = hs.Run(bench, cell, seed, 0.0, False, time.time())
        run.device = device
        run.override(args.set)
        kind = "score" if run.traffic["driver"] == "score" else "train"
        limits = run.cfg["limits"][kind]
        numbers = control_numbers(
            run, args.cars or run.cfg["deployment"]["cars"])
        fails = [k for k, v in numbers.items()
                 if k in limits and not v <= limits[k]]
        passed += not fails
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "platform": device["platform"],
                          "control": numbers, "limits": limits,
                          "not_correct_by": fails}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    raise SystemExit(main())
