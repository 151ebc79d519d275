"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process owns the chip: it runs the program's trainer or scorer
in-process (only the chip's owner can take its profiler trace) and
starts one CPU-pinned child that hosts the log, the fleet and, in an
open-loop cell, the paced producer and the watcher.  It knows no cell,
configuration or metric by name: `BENCHMARK.json` names them and each
is a file of its own under `benchmark/`.

The last line of stdout is the result object and nothing else.  Where
JAX finds no accelerator and `JAX_PLATFORMS=cpu` did not name the CPU,
it exits non-zero and prints no result; a run on a named CPU is a
rehearsal and carries no device metric.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)


def build_engine_if_missing() -> None:
    """The native engine is built once per checkout (its `.so` is not a
    committed file, and one built elsewhere may not run here)."""
    cpp = os.path.join(ROOT, "iotml", "cpp")
    if os.path.exists(os.path.join(cpp, "build", "libiotml_stream.so")):
        return
    make = subprocess.run(["make", "-B", "-C", cpp], text=True,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          timeout=300)
    if make.returncode != 0:
        raise SystemExit("the native engine did not build:\n"
                         + make.stdout[-2000:])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="by hand only: override a number of the traffic "
                         "file (a sweep's rate, a rehearsal's log_scale) "
                         "or, as cfg.<group>.<key>, of the configuration's")
    args = ap.parse_args(argv)

    from benchmark import harness as hs

    bench = hs.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = hs.find_cell(bench, args.workload)
    if cell is None:
        # cells proven on the chip that the driver's memory floor keeps
        # out of BENCHMARK.json stay runnable by hand
        bench = hs.load_json(os.path.join(BENCH, "unlisted.json"))
        cell = hs.find_cell(bench, args.workload)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    build_engine_if_missing()
    run = hs.Run(bench, cell, args.seed, args.seconds, bool(args.trace),
                 T_START)
    run.override(args.set)
    driver = hs.load_module(os.path.join(
        BENCH, "drivers", run.traffic["driver"] + ".py"))
    run.spawn_log(driver.log_spec(run))  # fills while JAX starts here
    try:
        result = measure(run, driver)
    finally:
        run.close()
    return report(run, result)


def measure(run, driver) -> dict:
    from benchmark import harness as hs

    cell = run.cell
    # the chip's one owner: claim it before anything else touches JAX;
    # this raises where JAX fell back to a CPU nobody named
    from iotml.stream import native
    from iotml.utils.device import claim_device

    import jax  # noqa: F401  (first, so the libraries below find it)

    # the backend's start waits on the device for seconds with the
    # interpreter lock released: import the configuration's heavy
    # third-party libraries meanwhile
    early = threading.Thread(target=lambda: [
        importlib.import_module(m) for m in run.cfg.get("preload", [])])
    early.start()
    device = claim_device()
    early.join()
    if device["count"] < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} chip(s), "
                         f"JAX found {device['count']}")
    if not native.available():
        raise SystemExit("the native stream engine did not load")
    hs.say("device:", json.dumps(device))

    run.device = device
    run.lap("imports, engine, backend")
    return driver.run(run)


def report(run, result: dict) -> int:
    from benchmark import harness as hs

    bench, cell = run.bench, run.cell
    def in_cell(m) -> bool:
        return cell["name"] in m.get("workloads", [cell["name"]])

    reported = {m["name"] for m in bench["end_to_end"] if in_cell(m)}
    names = bench["per_layer"] if run.trace else bench["end_to_end"]
    metrics = {}
    for m in names:
        # a per-layer metric belongs to the cells that report the
        # end-to-end metric it moves (or to those it lists)
        if not in_cell(m) or m.get("moves", m["name"]) not in reported:
            continue
        if run.trace:
            reader = hs.load_module(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py"))
            value = reader.read(run)
            if m["source"] == "device_trace" and not run.on_chip():
                value = None  # a rehearsal carries no device metric
        else:
            value = result["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": run.correct(), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": result["device"]}
    traced = run.notes.get("traced")
    if run.trace and traced and run.on_chip():
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    with open(run.path("result.json"), "w") as fh:
        json.dump({"line": line, "checks": run.checks}, fh, indent=1,
                  default=str)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
