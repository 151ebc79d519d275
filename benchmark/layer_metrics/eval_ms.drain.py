"""eval_ms.drain: device eval (dispatch, program, device_get) of a bounded drain."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "score", "device_compute", "bench.drain")
