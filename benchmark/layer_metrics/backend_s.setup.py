"""backend_s.setup: the `iotml.start.backend` span, `claim_device()`'s `jax.devices()`: the backend's initialisation until the process holds its chip."""

import os

from benchmark import harness as hs

_first = hs.load_module(os.path.join(hs.BENCH, "layer_metrics",
                                     "first_fit_s.setup.py"))


def read(run):
    return _first.span_seconds("backend")
