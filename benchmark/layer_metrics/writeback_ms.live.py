"""writeback_ms.live: the rest of a drain under the open loop."""

from benchmark.readers import rest_ms


def read(run):
    return rest_ms(run, "score", "bench.drain")
