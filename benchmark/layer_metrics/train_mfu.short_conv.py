"""train_mfu.short_conv: the operations a gated-short-convolution, rotary-attention, sparse-expert job's forward and backward passes require (short_conv_ops.train_ops_bytes, the experts by the assignments the program's counter read), over fit_ms.train and the chip's bf16 peak."""

from benchmark import kernels, short_conv_ops
from benchmark.readers import phase_ms

HELD = 'iotml_moe_assignments_total{kind="held"}'


def read(run):
    fit_ms = phase_ms(run, "train", "device_compute", "bench.round")
    job, rounds = run.cfg["job"], run.notes.get("rounds")
    held = run.notes.get("registry", {}).get(HELD)
    # nothing to read: a program without expert layers has no such
    # counter, a rehearsal no chip, another configuration no short
    # convolution
    if fit_ms is None or held is None or not rounds \
            or "conv_L_cache" not in run.cfg or not run.on_chip():
        return None
    tokens = job["batch_size"] * job["take_batches"] * job["window"] \
        * job["epochs"]
    ops = short_conv_ops.train_ops_bytes(run.cfg, job["window"], tokens,
                                         held / rounds)["ops"]
    peak = kernels.peaks(run.device["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (fit_ms * 1e-3) / peak
