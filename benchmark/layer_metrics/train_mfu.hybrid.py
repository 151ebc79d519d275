"""train_mfu.hybrid: the operations a hybrid (state-space + attention) job's forward and backward passes require (hybrid_ops.train_ops_bytes), over fit_ms.train and the chip's bf16 peak."""

from benchmark import hybrid_ops, kernels
from benchmark.readers import phase_ms


def read(run):
    fit_ms = phase_ms(run, "train", "device_compute", "bench.round")
    job = run.cfg["job"]
    if fit_ms is None or "mamba_n_heads" not in run.cfg \
            or not run.on_chip():
        return None
    tokens = job["batch_size"] * job["take_batches"] * job["window"] \
        * job["epochs"]
    ops = hybrid_ops.train_ops_bytes(run.cfg, job["window"], tokens)["ops"]
    peak = kernels.peaks(run.device["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (fit_ms * 1e-3) / peak
