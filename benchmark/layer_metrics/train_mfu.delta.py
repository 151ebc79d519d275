"""train_mfu.delta: the operations a Kimi-Delta-Attention, latent-attention, sparse-expert job's forward and backward passes require (delta_ops.train_ops_bytes: the delta rule by the chunked algorithm at the chunk size the program's gauge `iotml_kda_chunk_size` holds, over the layers `iotml_model_layers{kind="kda"}` holds; the experts by the assignments the program's counter read), over fit_ms.train and the chip's bf16 peak."""

from benchmark import delta_ops, kernels
from benchmark import harness as hs
from benchmark.readers import phase_ms

HELD = 'iotml_moe_assignments_total{kind="held"}'
CHUNK, LAYERS = "iotml_kda_chunk_size", 'iotml_model_layers{kind="kda"}'


def read(run):
    fit_ms = phase_ms(run, "train", "device_compute", "bench.round")
    job, rounds = run.cfg["job"], run.notes.get("rounds")
    held = run.notes.get("registry", {}).get(HELD)
    # gauges hold what they were last set to: the process's registry at
    # the read, not the window's difference
    said = hs.registry()
    chunk, layers = said.get(CHUNK), said.get(LAYERS)
    # nothing to read: a program without the delta rule has no such
    # gauges, a rehearsal no chip, another configuration no such layers
    if fit_ms is None or held is None or not rounds or not chunk \
            or not layers or "linear_attn_config" not in run.cfg \
            or not run.on_chip():
        return None
    tokens = job["batch_size"] * job["take_batches"] * job["window"] \
        * job["epochs"]
    ops = delta_ops.train_ops_bytes(run.cfg, job["window"], tokens,
                                    held / rounds, int(chunk),
                                    int(layers))["ops"]
    peak = kernels.peaks(run.device["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (fit_ms * 1e-3) / peak
