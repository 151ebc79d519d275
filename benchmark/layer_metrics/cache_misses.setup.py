"""cache_misses.setup: lookups of the persistent compile cache that missed, over the program's own programs (`iotml_compile_cache_total{result="miss",program="iotml_*"}`): 0 in a warm run — the number that says whether a line's `setup_s` compiled."""

import re

from benchmark import harness as hs

SERIES = re.compile(r'iotml_compile_cache_total'
                    r'\{program="(iotml_\w+)",result="miss"\}')


def read(run):
    from iotml.obs import metrics

    # a program that counts its lookups without naming the program (the
    # parent of the PR that brought the label): nothing to read
    if "program" not in getattr(metrics, "DECLARED_METRIC_LABELS", {}).get(
            "compile_cache", ()):
        return None
    missed = {SERIES.fullmatch(k).group(1): v
              for k, v in hs.registry().items() if SERIES.fullmatch(k)}
    if missed:
        hs.say("compiled (cache miss):", ", ".join(
            f"{name} x{int(n)}" for name, n in sorted(missed.items())))
    return float(sum(missed.values()))
