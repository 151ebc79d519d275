"""train_mfu.window: the operations a global-and-sliding-window attention, sparse-expert job's forward and backward passes require (window_ops.train_ops_bytes: a window layer's attention by the keys inside its mask, the experts by the assignments the program's counter read), over fit_ms.train and the chip's bf16 peak."""

from benchmark import kernels, window_ops
from benchmark.readers import phase_ms

HELD = 'iotml_moe_assignments_total{kind="held"}'


def read(run):
    fit_ms = phase_ms(run, "train", "device_compute", "bench.round")
    job, rounds = run.cfg["job"], run.notes.get("rounds")
    held = run.notes.get("registry", {}).get(HELD)
    # nothing to read: a program without expert layers has no such
    # counter, a rehearsal no chip, another configuration no window
    if fit_ms is None or held is None or not rounds \
            or "sliding_window_layout" not in run.cfg or not run.on_chip():
        return None
    tokens = job["batch_size"] * job["take_batches"] * job["window"] \
        * job["epochs"]
    ops = window_ops.train_ops_bytes(run.cfg, job["window"], tokens,
                                     held / rounds)["ops"]
    peak = kernels.peaks(run.device["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (fit_ms * 1e-3) / peak
