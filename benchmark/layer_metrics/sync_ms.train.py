"""sync_ms.train: the one device_get a round waits in until the device is done; the program's sync phase, part of fit_ms.train."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "sync", "bench.round")
