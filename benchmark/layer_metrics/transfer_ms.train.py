"""transfer_ms.train: the host's device_put call of a round; the program's transfer phase, part of fit_ms.train."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "transfer", "bench.round")
