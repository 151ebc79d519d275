"""dispatch_ms.train: the fused or scanned fit's call until it returns to the host; the program's dispatch phase, part of fit_ms.train."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "dispatch", "bench.round")
