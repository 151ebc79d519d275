"""train_mfu: the operations a job's forward and backward passes require (kernels.transformer_train_ops), over fit_ms.train and the chip's bf16 peak."""

from benchmark import kernels
from benchmark.readers import phase_ms


def read(run):
    fit_ms = phase_ms(run, "train", "device_compute", "bench.round")
    model, job = run.cfg.get("model", {}), run.cfg["job"]
    if fit_ms is None or "d_model" not in model or not run.on_chip():
        return None
    tokens = job["batch_size"] * job["take_batches"] * job["window"] \
        * job["epochs"]
    ops = kernels.transformer_train_ops(model, job["window"], tokens)
    peak = kernels.peaks(run.device["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (fit_ms * 1e-3) / peak
