"""fit_ms.train: transfer + fit program + the one sync of a round: the program's device_compute phase."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "device_compute", "bench.round")
