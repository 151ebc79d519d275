"""The benchmark's own tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of tier-1 (ROADMAP's line names `tests/` only)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture
def rehearsed_layer_metrics():
    """The per-layer metrics a traced rehearsal of a cell reports, read
    from the file that lists the cell: every entry that is the cell's
    (it names the cell, or names none) and moves an end-to-end metric
    the cell reports — but for the shares of a chip's peak, which a CPU
    has none of."""
    def names(bench: dict, workload: str) -> set:
        def mine(m):
            return workload in m.get("workloads", [workload])

        reported = {m["name"] for m in bench["end_to_end"] if mine(m)}
        return {m["name"] for m in bench["per_layer"]
                if mine(m) and m["moves"] in reported
                and not m["name"].startswith("train_mfu")}
    return names
