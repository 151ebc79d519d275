"""compile_s.setup: seconds JAX spent building or loading the program's own programs (`iotml_compile_seconds_sum{stage="backend"}` over the `iotml_*` programs, the process's total at the read; it covers `cache_read`): a few seconds where the persistent cache served, a minute or more where it did not."""

import os

from benchmark import harness as hs

_traced = hs.load_module(os.path.join(hs.BENCH, "layer_metrics",
                                      "trace_lower_s.setup.py"))


def read(run):
    return _traced.told(("backend",), "built or loaded, by program:")
