"""eval_ms.live: device eval of a drain under the open loop."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "score", "device_compute", "bench.drain")
