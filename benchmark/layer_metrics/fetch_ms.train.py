"""fetch_ms.train: the consumer's calls of a round (poll_into, poll_decoded, poll): the wire and, on the native paths, the decode fused into the same C++ call; the program's fetch phase."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "fetch", "bench.round")
