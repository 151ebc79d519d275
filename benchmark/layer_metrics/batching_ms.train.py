"""batching_ms.train: poll + decode + batch assembly of a round: the program's host_pipeline phase."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "host_pipeline", "bench.round")
