"""decode_ms.train: decode where it is a call of its own (the message-list leg); None where the native client fuses it into the fetch, and the harness then leaves the metric out; the program's decode phase."""

from benchmark.readers import phase_ms


def read(run):
    return phase_ms(run, "train", "decode", "bench.round")
