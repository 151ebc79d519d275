"""train_mfu.loop: the operations a looped stack's job requires — one pass over the layers (loop_ops.train_ops_bytes) times the passes the program's own gauge `iotml_model_loop_steps` says it made — over fit_ms.train and the chip's bf16 peak; a program that ran fewer passes reads lower."""

from benchmark import harness as hs
from benchmark import kernels, loop_ops
from benchmark.readers import phase_ms

PASSES = "iotml_model_loop_steps"


def read(run):
    fit_ms = phase_ms(run, "train", "device_compute", "bench.round")
    job = run.cfg["job"]
    # a gauge holds what it was last set to, so the process's registry
    # at the read and not the window's difference; a program without a
    # loop has no such gauge
    passes = hs.registry().get(PASSES)
    # nothing to read: no such gauge, a rehearsal without a chip, no
    # spans, another configuration
    if fit_ms is None or passes is None or "total_ut_steps" not in run.cfg \
            or not run.on_chip():
        return None
    tokens = job["batch_size"] * job["take_batches"] * job["window"] \
        * job["epochs"]
    ops = loop_ops.train_ops_bytes(run.cfg, job["window"], tokens,
                                   passes)["ops"]
    peak = kernels.peaks(run.device["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (fit_ms * 1e-3) / peak
