"""What the per-layer readers share: each file under `layer_metrics/`
is one metric's reader, `read(run) -> value or None`, and most of them
read one of these.  A reader that finds nothing to read returns None and
the harness leaves its metric out of the line."""

from __future__ import annotations


def _calls(run, span: str) -> int:
    return run.notes.get("spans", {}).get(span, (0.0, 0))[1]


def phase_ms(run, loop: str, phase: str, per_span: str):
    """Milliseconds of one phase of `iotml_step_seconds` (the program's
    own histogram, its sum over the window) per call of `per_span`."""
    key = f'iotml_step_seconds_sum{{loop="{loop}",phase="{phase}"}}'
    total, calls = run.notes.get("registry", {}).get(key), \
        _calls(run, per_span)
    if total is None or not calls:
        return None
    return total * 1e3 / calls


def rest_ms(run, loop: str, span: str):
    """The benchmark's span around the call, less the two phases the
    program times inside it, per call: timed from outside by
    subtraction until the program has spans of its own there."""
    total, calls = run.notes.get("spans", {}).get(span, (0.0, 0))
    inner = [phase_ms(run, loop, p, span)
             for p in ("host_pipeline", "device_compute")]
    if not calls or None in inner:
        return None
    return total * 1e3 / calls - sum(inner)
