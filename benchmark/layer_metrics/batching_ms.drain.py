"""batching_ms.drain: poll + decode + batching of a bounded drain."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "score", "host_pipeline", "bench.drain")
