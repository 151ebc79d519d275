"""writeback_ms.drain: the rest of a bounded drain: errors, per-car detector, format, ordered write-back, commit."""

from benchmark.readers import rest_ms


def read(run):
    return rest_ms(run, "score", "bench.drain")
