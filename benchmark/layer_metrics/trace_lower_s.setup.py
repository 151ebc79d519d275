"""trace_lower_s.setup: seconds JAX spent tracing and lowering the program's own jitted functions (`iotml_compile_seconds_sum{stage="trace"}` + `{stage="lower"}` over the `iotml_*` programs, the process's total at the read): paid at every start, warm or not; with `recompiles.train` 0 all of it is set-up's, and the reference's program is `other` and left out."""

import re

from benchmark import harness as hs

SERIES = re.compile(r'iotml_compile_seconds_sum'
                    r'\{program="(iotml_\w+)",stage="(\w+)"\}')


def compile_seconds(stages) -> dict:
    """Program -> seconds at `stages`, over the program's own programs;
    {} where nothing was compiled, None where the program does not count
    its compilations by program."""
    from iotml.obs import metrics

    if "program" not in getattr(metrics, "DECLARED_METRIC_LABELS", {}).get(
            "compile_seconds", ()):
        return None
    out = {}
    for key, value in hs.registry().items():
        found = SERIES.fullmatch(key)
        if found and found.group(2) in stages:
            out[found.group(1)] = out.get(found.group(1), 0.0) + value
    return out


def told(stages, what: str):
    """The sum over the programs, with a story line that names each."""
    by = compile_seconds(stages)
    if by is None:
        return None
    hs.say(what, ", ".join(
        f"{name} {sec:.2f} s" for name, sec in sorted(by.items())) or "none")
    return float(sum(by.values()))


def read(run):
    return told(("trace", "lower"), "traced and lowered, by program:")
