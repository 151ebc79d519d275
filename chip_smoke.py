#!/usr/bin/env python3
"""Chip smoke: the live train -> score path, end to end, on the accelerator.

    python chip_smoke.py            # one chip; the driver's check
    python chip_smoke.py --mesh-data 4    # the multi-chip live trainer

Drives the system through the entry points a user calls, at the full
width of the model every live entry point serves (CAR_AUTOENCODER,
18->14->7->7->18, batch 100, --normalize full):

    cli.up (MQTT fleet -> bridge -> KSQL -> SENSOR_DATA_S_AVRO)   host, CPU
    cli.live train  (SensorBatches -> Trainer.fit_compiled -> artifact) chip
    cli.live score  (artifact -> StreamScorer -> model-predictions)     chip

This process is an orchestrator and never imports jax: a chip belongs to
one process at a time, so every phase that needs it is a child that owns
it alone and has exited before the next one starts; the host plane is a
child pinned to the CPU.  Nothing is caught and skipped — the first
failed phase ends the run with that child's stderr tail, exit code 1 and
no result line.  On success stdout carries two JSON lines: the run's
summary (versions, rounds, losses, rows, fit, cache entries, set-up
seconds; also written to <out>/summary.json), then, LAST, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reports it — the line the driver reads, with
those keys and no others.

`--platform cpu` rehearses the orchestration on a box without a chip
(Pallas interpreted); its result line says "cpu" and proves nothing
about the device.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CPP_DIR = os.path.join(REPO, "iotml", "cpp")
ENGINE_SO = os.path.join(CPP_DIR, "build", "libiotml_stream.so")
TOPIC = "SENSOR_DATA_S_AVRO"
PREDICTIONS = "model-predictions"

# the fleet: 500 cars at 10 Hz through the real MQTT front (a few
# thousand records/s on one host core) fills a 100x100-record round
# every few seconds
FLEET_CARS, FLEET_HZ = 500, 10
MIN_ROUNDS = 3          # train rounds that must print before STOP
MIN_SCORED = 5_000      # live rows the scorer must write before STOP

# per-phase limits; their sum stays inside the driver's 1200 s
T_PROBE, T_BUILD, T_PLANE, T_TRAIN, T_SCORE, T_STOP, T_VERIFY = \
    180, 180, 60, 360, 240, 60, 60

# runs in a CPU-pinned child: the repo's own wire client and registry
# reader check what the chip phases left behind
_VERIFY_SRC = r"""
import json, sys
from iotml.stream.kafka_wire import KafkaWireBroker
addr, topic, source, registry = sys.argv[1:5]
b = KafkaWireBroker(addr)
parts = range(b.topic(topic).partitions)
ends = {p: b.end_offset(topic, p) for p in parts}
payloads = [m.value for p in parts if ends[p]
            for m in b.fetch(topic, p, 0, 8)]
out = {"predictions_end": sum(ends.values()),
       "payloads_checked": len(payloads),
       "payloads_ok": bool(payloads)
       and all(v.startswith(b"[") for v in payloads),
       "source_partitions": b.topic(source).partitions}
if registry:
    from iotml.mlops import ModelRegistry
    reg = ModelRegistry(registry)
    m = reg.manifest(reg.latest())
    out["manifest_version"] = m.version
    out["manifest_partitions"] = sorted(p for t, p, _ in m.offsets
                                        if t == source)
print(json.dumps(out))
"""


class Failure(Exception):
    """A phase failed; the message names it."""


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class Child:
    """One child process: stdout collected line by line, stderr in a
    file under the output directory, its own process group so nothing
    it starts outlives the run."""

    live: list = []

    def __init__(self, name: str, argv: list, platform: str, out_dir: str,
                 stdin: bool = False):
        self.name = name
        self.err_path = os.path.join(out_dir, f"{name}.err")
        self.lines: list = []
        env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONUNBUFFERED="1",
                   PYTHONPATH=REPO + os.pathsep +
                   os.environ.get("PYTHONPATH", ""))
        with open(self.err_path, "w") as err:
            self.proc = subprocess.Popen(
                [sys.executable, *argv], cwd=REPO, env=env,
                stdin=subprocess.PIPE if stdin else subprocess.DEVNULL,
                stdout=subprocess.PIPE, stderr=err, text=True, bufsize=1,
                start_new_session=True)
        self.t0 = time.monotonic()
        Child.live.append(self)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))

    def stats(self) -> list:
        """The JSON lines printed so far."""
        return [json.loads(l) for l in list(self.lines)
                if l.startswith("{")]

    def err_tail(self, n: int = 2500) -> str:
        try:
            with open(self.err_path, errors="replace") as fh:
                return fh.read()[-n:].strip()
        except OSError:
            return ""

    def fail(self, why: str) -> Failure:
        return Failure(f"{self.name}: {why}\n--- {self.name} stdout tail\n"
                       + "\n".join(self.lines[-5:])[-1500:]
                       + f"\n--- {self.name} stderr tail\n{self.err_tail()}")

    def wait_for(self, cond, timeout: float, what: str):
        """Poll until cond() is truthy; a dead child or the deadline
        fails the run."""
        deadline = time.monotonic() + timeout
        while True:
            got = cond()
            if got:
                return got
            if self.proc.poll() is not None:
                self._reader.join(timeout=5)
                got = cond()
                if got:
                    return got
                raise self.fail(f"exited rc={self.proc.returncode} "
                                f"before {what}")
            if time.monotonic() > deadline:
                raise self.fail(f"timed out after {timeout}s waiting "
                                f"for {what}")
            time.sleep(0.1)

    def finish(self, timeout: float, stop_line: bool = False) -> None:
        """Wait for a clean exit (after a STOP line if asked)."""
        if stop_line:
            try:
                self.proc.stdin.write("STOP\n")
                self.proc.stdin.flush()
            except OSError:
                pass  # already gone: the return code below says how
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise self.fail(f"still running {timeout}s after it was "
                            "asked to stop") from None
        self._reader.join(timeout=5)
        if rc != 0:
            raise self.fail(f"exited rc={rc}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()


# ------------------------------------------------------------------ phases
def probe_device(platform: str, out_dir: str) -> dict:
    """What JAX finds, asked of a child that exits before the next one
    needs the chip.  No accelerator -> the run ends here, before the
    platform starts."""
    child = Child("probe", ["-m", "iotml.utils.device"], platform, out_dir)
    try:
        child.finish(T_PROBE)
    except Failure as e:
        raise Failure(f"JAX could not start the {platform!r} backend "
                      f"(no accelerator for this process?)\n{e}") from None
    report = child.stats()[-1]
    if report["platform"] != platform:
        raise child.fail(f"asked for {platform!r}, JAX reports "
                         f"{report['platform']!r}")
    return report


def build_native_engine(out_dir: str) -> int:
    """Force-rebuild the C++ engine from the tracked sources: a prebuilt
    .so (git-ignored, -march=native) may come from another machine, and
    the mtime staleness check cannot know.  Returns the engine's ABI
    version; a failed build fails the run (the columnar plane is the
    main path, the pure-Python decode is not a pass)."""
    try:
        make = subprocess.run(["make", "-B", "-C", CPP_DIR], text=True,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, timeout=T_BUILD)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise Failure(f"native engine build did not run: {e}") from None
    with open(os.path.join(out_dir, "build.log"), "w") as logf:
        logf.write(make.stdout)
    if make.returncode != 0:
        raise Failure(f"native engine build failed rc={make.returncode}\n"
                      + make.stdout[-2500:])
    lib = ctypes.CDLL(ENGINE_SO)
    lib.iotml_engine_version.restype = ctypes.c_int64
    return int(lib.iotml_engine_version())


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_host_plane(out_dir: str) -> tuple:
    port = free_port()
    plane = Child("plane", ["-m", "iotml.cli.up", "--kafka-port", str(port),
                            "--metrics-port", "0",
                            "--fleet", str(FLEET_CARS),
                            "--rate", str(FLEET_HZ)], "cpu", out_dir)

    def listening():
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return True
        except OSError:
            return False

    plane.wait_for(listening, T_PLANE, f"the Kafka wire port {port}")
    return plane, f"127.0.0.1:{port}"


def check_device_line(child: Child, first: dict, probe: dict) -> None:
    dev = first.get("device")
    if not dev:
        raise child.fail("first stats line carries no device")
    for key in ("platform", "device_kind", "count"):
        if dev[key] != probe[key]:
            raise child.fail(f"ran on {dev}, the probe found {probe}")
    if not dev["native_engine"]:
        raise child.fail("native stream engine not active")


def train_phase(addr: str, probe: dict, out_dir: str, mesh_data: int) -> dict:
    argv = ["-m", "iotml.cli.live", "train", addr, TOPIC,
            os.path.join(out_dir, "artifacts"), "--normalize", "full",
            "--take-batches", "100", "--stats"]
    if mesh_data:
        argv += ["--mesh-data", str(mesh_data), "--device-normalize", "1",
                 "--registry", os.path.join(out_dir, "registry")]
    # stdin held open: the CLI stops on EOF
    train = Child("train", argv, probe["platform"], out_dir, stdin=True)
    first = train.wait_for(train.stats, T_TRAIN, "the first round")[0]
    first_round_s = time.monotonic() - train.t0
    train.wait_for(lambda: len(train.stats()) >= MIN_ROUNDS, T_TRAIN,
                   f"{MIN_ROUNDS} rounds")
    train.finish(T_STOP, stop_line=True)
    rounds = train.stats()
    check_device_line(train, first, probe)
    losses = [r["loss"] for r in rounds]
    if not all(math.isfinite(l) for l in losses):
        raise train.fail(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise train.fail(f"loss did not fall: {losses}")
    interpret = probe["platform"] == "cpu" and not mesh_data
    want = ("sharded" if mesh_data else "fused", interpret)
    got = {(r["fit"], r["interpret"]) for r in rounds}
    if got != {want}:
        raise train.fail(f"rounds ran {sorted(got)}, expected {want}")
    if mesh_data:
        for r in rounds:
            if not (len(set(r["shard_devices"])) == len(r["shard_records"])
                    == len(r["shard_losses"]) == mesh_data
                    and all(r["shard_records"])
                    and all(map(math.isfinite, r["shard_losses"]))):
                raise train.fail(
                    f"round {r['round']}: expected {mesh_data} devices, "
                    f"each with rows of its own and a finite loss; got "
                    f"losses {r['shard_losses']}, records "
                    f"{r['shard_records']} on devices {r['shard_devices']}")
    return {"rounds": len(rounds), "loss_first": losses[0],
            "loss_last": losses[-1],
            "records_trained": rounds[-1]["records_cum"],
            "fit": want[0], "interpret": want[1],
            "first_round_s": round(first_round_s, 1),
            **({k: rounds[-1][k] for k in ("shard_losses", "shard_records",
                                            "shard_devices")}
               if mesh_data else {})}


def score_phase(addr: str, probe: dict, out_dir: str) -> dict:
    score = Child("score", ["-m", "iotml.cli.live", "score", addr, TOPIC,
                            PREDICTIONS, os.path.join(out_dir, "artifacts"),
                            "--normalize", "full", "--car-feature-heads",
                            "--stats"], probe["platform"], out_dir,
                  stdin=True)
    first = score.wait_for(score.stats, T_SCORE, "the first drain")[0]
    first_drain_s = time.monotonic() - score.t0
    score.wait_for(lambda: score.stats()[-1]["scored"] >= MIN_SCORED,
                   T_SCORE, f"{MIN_SCORED} rows scored")
    score.finish(T_STOP, stop_line=True)
    check_device_line(score, first, probe)
    last = score.stats()[-1]
    return {"rows_scored": last["scored"], "artifact": last["artifact"],
            "model_updates": last["model_updates"],
            "first_drain_s": round(first_drain_s, 1)}


def verify_phase(addr: str, out_dir: str, scored: int, mesh_data: int) -> dict:
    registry = os.path.join(out_dir, "registry") if mesh_data else ""
    verify = Child("verify", ["-c", _VERIFY_SRC, addr, PREDICTIONS, TOPIC,
                              registry], "cpu", out_dir)
    verify.finish(T_VERIFY)
    got = verify.stats()[-1]
    if got["predictions_end"] != scored:
        raise verify.fail(f"predictions end offset {got['predictions_end']}"
                          f" != rows scored {scored}")
    if not got["payloads_ok"]:
        raise verify.fail("prediction payloads do not begin with '['")
    if mesh_data and got["manifest_partitions"] != \
            list(range(got["source_partitions"])):
        raise verify.fail(
            f"registry manifest v{got['manifest_version']} stamps "
            f"partitions {got['manifest_partitions']} of "
            f"{got['source_partitions']}")
    return got


def cache_entries(path) -> int:
    """Programs in the compile cache (a CPU-pinned run keeps none: its
    path is None)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


# -------------------------------------------------------------------- main
def result_line(probe: dict) -> dict:
    """The LAST stdout line: these keys and no others (the driver
    refuses anything else); the rest of the run is the summary line."""
    return {"ok": True,
            "device": {"platform": str(probe["platform"]),
                       "kind": str(probe["device_kind"]),
                       "count": int(probe["count"])}}


def run(args) -> dict:
    t_all = time.monotonic()
    out_dir = os.path.abspath(args.out)
    for sub in ("artifacts", "registry"):
        # a stale artifact or manifest would be loaded as this run's model
        shutil.rmtree(os.path.join(out_dir, sub), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    probe = probe_device(args.platform, out_dir)
    log(f"device: {probe['count']}x {probe['device_kind']} "
        f"({probe['platform']}), versions {probe['versions']}")
    if args.mesh_data > probe["count"]:
        raise Failure(f"--mesh-data {args.mesh_data} but JAX reports "
                      f"{probe['count']} device(s)")
    cache_dir = probe["compile_cache_dir"]
    cache_before = cache_entries(cache_dir)
    engine_version = build_native_engine(out_dir)
    log(f"native engine rebuilt (ABI {engine_version}); compile cache "
        f"{cache_dir} holds {cache_before} entries")

    plane, addr = start_host_plane(out_dir)
    log(f"host plane up on {addr} (cpu-pinned)")
    trained = train_phase(addr, probe, out_dir, args.mesh_data)
    log(f"train ok: {trained}")
    scored = score_phase(addr, probe, out_dir)
    log(f"score ok: {scored}")
    verified = verify_phase(addr, out_dir, scored["rows_scored"],
                            args.mesh_data)
    log(f"verify ok: {verified}")
    # the in-memory platform holds nothing worth a graceful stop, and a
    # SIGINT would be ignored where this script itself runs with it off
    plane.kill()

    cache_after = cache_entries(cache_dir)
    return {
        **result_line(probe),
        "versions": probe["versions"],
        **trained, **scored,
        "predictions_end_offset": verified["predictions_end"],
        "native_engine": True, "native_engine_abi": engine_version,
        "mesh_data": args.mesh_data,
        **({"manifest_partitions": verified["manifest_partitions"]}
           if args.mesh_data else {}),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": cache_after,
        "compile_cache_added": cache_after - cache_before,
        "wall_s": round(time.monotonic() - t_all, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu rehearses the orchestration without a chip")
    ap.add_argument("--mesh-data", type=int, default=0, metavar="N",
                    help="train on an N-device data mesh (cli.live train "
                         "--mesh-data N --device-normalize 1 --registry)")
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                    help="artifacts, registry and child logs go here")
    args = ap.parse_args(argv)
    if not os.path.isdir(CPP_DIR):
        log(f"FAIL: no iotml package beside {__file__} — run from a "
            "checkout of the repo")
        return 1
    try:
        summary = run(args)
    except Failure as e:
        log(f"FAIL: {e}")
        return 1
    finally:
        for child in Child.live:
            child.kill()
    with open(os.path.join(args.out, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps({"summary": summary}))
    print(json.dumps({k: summary[k] for k in ("ok", "device")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
