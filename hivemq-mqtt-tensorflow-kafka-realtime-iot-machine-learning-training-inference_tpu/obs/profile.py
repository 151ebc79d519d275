"""Profiler integration — the reference's TensorBoard-profiler story.

The reference commits actual TF profiler traces with its notebooks
(`python-scripts/autoencoder-anomaly-detection/logs/plugins/profile/...`,
SURVEY §5 'tracing/profiling') and calls training monitoring a roadmap item
(reference `README.md:116`).  Here the JAX profiler fills that role: traces
are written in the same TensorBoard `plugins/profile` layout, viewable with
`tensorboard --logdir` + the profile plugin, or in Perfetto.

Usage:

    from iotml.obs.profile import trace

    with trace("./logs"):                  # one captured window
        trainer.fit_compiled(batches, epochs=20)

The program's own phases (`iotml.train.fit`, `.host_pipeline`, `.fetch`,
`.decode`, `.dispatch`, ... — `obs.tracing.phase`) are already spans of
that capture's host plane, and so are a start's where the capture is
open that early: `iotml.start.backend`, `.state_init`, `.engine` and,
one per import of 0.1 s or more, `.import` (its module in the
annotation's `note`); `annotate` adds a caller's own.

`jax` is imported where a capture starts, not with this module: the
`obs` package stays importable ahead of everything heavy, which is what
lets `tracing.time_imports()` see the heavy imports.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

from . import tracing


@contextlib.contextmanager
def trace(logdir: str = "./logs") -> Iterator[None]:
    """Capture a profiler trace window into `logdir` (TensorBoard layout)."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named span on the trace timeline's host plane: the annotation
    `tracing.phase` opens, for a caller's own code."""
    return tracing.annotation(name)


@contextlib.contextmanager
def maybe_trace(logdir: Optional[str]) -> Iterator[None]:
    """`trace` when a directory is given, no-op otherwise — for call sites
    driven by an optional setting."""
    if logdir:
        with trace(logdir):
            yield
    else:
        yield


def trace_files(logdir: str) -> list:
    """Paths of captured trace artifacts under a log directory."""
    out = []
    for root, _dirs, files in os.walk(logdir):
        for f in files:
            if ".trace" in f or f.endswith((".pb", ".json.gz", ".xplane.pb")):
                out.append(os.path.join(root, f))
    return sorted(out)
