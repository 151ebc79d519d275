"""state_init_s.setup: the `iotml.start.state_init` span of `Trainer._ensure_state`: `iotml_state_init` traced, lowered, read from the cache or compiled, and run until the state is on the device, with the step's construction."""

import os

from benchmark import harness as hs

_first = hs.load_module(os.path.join(hs.BENCH, "layer_metrics",
                                     "first_fit_s.setup.py"))


def read(run):
    return _first.span_seconds("state_init")
