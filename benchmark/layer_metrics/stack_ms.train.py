"""stack_ms.train: stacking a round's batches (xs, masks, ys) into the arrays one transfer ships; the program's stack phase, the inside twin of round_rest_ms.train."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "stack", "bench.round")
