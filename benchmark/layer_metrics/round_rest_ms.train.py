"""round_rest_ms.train: the rest of a round: stacking, publish, pointer flip, commit."""

from benchmark.readers import rest_ms


def read(run):
    return rest_ms(run, "train", "bench.round")
