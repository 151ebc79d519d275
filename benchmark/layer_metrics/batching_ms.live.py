"""batching_ms.live: poll + decode + batching of a drain under the open loop."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "score", "host_pipeline", "bench.drain")
