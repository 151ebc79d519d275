"""first_fit_s.setup: the first `iotml.train.fit` span in the ring — the first job of the process, with the tracing, the lowering, the cache read or the compilation of the fit's program under its `dispatch` and the first execution under its `sync`; the run's story prints `set-up by span:`, where the seconds of `setup_s` went by the program's `iotml.start.*` spans, and what of it no span names."""

from benchmark import harness as hs
from benchmark.trace_reduce import union_ns

START = "iotml.start."
FIT = "iotml.train.fit"
GAP_S = 0.5   # an unnamed stretch shorter than this is not listed


def spans():
    """The program's phase spans, oldest first; None where the program
    has no ring to read (no `tracing.phases`)."""
    from iotml.obs import tracing

    return tracing.phases() if hasattr(tracing, "phases") else None


def has_start_spans() -> bool:
    """Whether this checkout's program times its start at all: one that
    does reports 0.0 where a phase took nothing, one that does not (the
    parent of the PR that brought them) reports nothing."""
    from iotml.obs import tracing

    return hasattr(tracing, "time_imports")


def span_seconds(phase: str):
    """Seconds of the process's `iotml.start.<phase>` spans, summed over
    threads; None where the program has no such spans."""
    if not has_start_spans():
        return None
    return float(sum(s.seconds for s in spans() if s.name == START + phase))


def first_fit():
    ring = spans()
    return next((s for s in ring or () if s.name == FIT), None)


def _wall(s) -> tuple:
    """A span on the wall clock, in seconds: (start, end)."""
    start = s.wall_ns() / 1e9
    return start, start + s.seconds


def story(run) -> None:
    """The line `set-up by span:` — the program's own account of its
    start (`tracing.start_report()`: each phase's seconds, the slowest
    imports by module, the first fit's dispatch and sync), and over it
    the wall clock: how much of `setup_s` the program's spans cover (a
    union, not a sum), how long imports ran beside the backend's start,
    and the stretches no span covers, at the offsets the `set-up +` lap
    lines above count in."""
    from iotml.obs import tracing

    setup_s = getattr(run, "setup_s", None)
    if setup_s is None or not has_start_spans():
        return
    told = tracing.start_report()
    t0, t1 = run.t_start, run.t_start + setup_s
    inside = [(a, b, s) for a, b, s in
              ((*_wall(s), s) for s in spans()) if a < t1 and b > t0]
    walls = {what: [(a, b) for a, b, s in inside if s.name == START + what]
             for what in ("import", "backend")}
    both = sum(max(0.0, min(b, d) - max(a, c))
               for a, b in union_ns(walls["import"])[1]
               for c, d in union_ns(walls["backend"])[1])
    total, covered = union_ns((max(a, t0), min(b, t1))
                              for a, b, _ in inside)
    # what lies bare, and the span that ended where each stretch begins
    edges = [t0] + [x for pair in covered for x in pair] + [t1]
    bare = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b - a < GAP_S:
            continue
        before = max((x for x in inside if x[1] <= a + 1e-6),
                     key=lambda x: x[1], default=None)
        bare.append(f"+{a - t0:.2f} to +{b - t0:.2f} after " + (
            "the process's start" if before is None else
            f"{before[2].name[len('iotml.'):]} {before[2].note or ''}"
            .strip()))
    hs.say(
        f"set-up by span: setup_s {setup_s:.2f}; "
        + "; ".join(f"{k} {v:.2f}" for k, v in sorted(told["start"].items()))
        + f" (import beside backend {both:.2f}); first fit "
        f"{told['first_fit_s']:.2f} (dispatch {told['dispatch_s']:.2f}, "
        f"sync {told['sync_s']:.2f}); slowest imports: "
        + (", ".join(f"{name} {sec:.2f}" for name, sec in told["imports"])
           or "none")
        + f"; the program's spans cover {total:.2f} s of setup_s "
        f"({100.0 * total / setup_s:.1f}%, a union on the wall clock); "
        f"bare stretches of {GAP_S} s or more, by the lap lines' clock: "
        + ("; ".join(bare) or "none"))


def read(run):
    fit = first_fit()
    if fit is None:
        return None
    story(run)
    return float(fit.seconds)
