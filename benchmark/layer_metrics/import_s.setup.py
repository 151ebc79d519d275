"""import_s.setup: the process's `iotml.start.import` spans, summed over threads — one span an OUTERMOST import of 0.1 s or more (obs/tracing.py `time_imports`), whose note names the module and what it pulled in; a preload on a side thread runs beside the backend's start, so the sum may overlap `backend_s.setup` (the story line `set-up by span:` says by how much)."""

import os

from benchmark import harness as hs

_first = hs.load_module(os.path.join(hs.BENCH, "layer_metrics",
                                     "first_fit_s.setup.py"))


def read(run):
    return _first.span_seconds("import")
