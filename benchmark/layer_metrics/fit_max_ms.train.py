"""fit_max_ms.train: the slowest `iotml.train.fit` span of the window's jobs, so that a stalled job shows in the line; the run's story prints which of its phases held it, beside the median job's."""

import collections
import statistics

from benchmark import harness as hs


def read(run):
    from iotml.obs import tracing

    rounds = run.notes.get("rounds")
    if not rounds or not hasattr(tracing, "phases"):
        return None  # a program without phase spans: nothing to read
    spans = tracing.phases()
    fits = [s for s in spans if s.name == "iotml.train.fit"][-rounds:]
    if not fits:
        return None
    slow = max(fits, key=lambda s: s.seconds)
    # each phase's seconds per job (a job's fetch spans summed)
    by_id, jobs = {s.id: s for s in spans}, {f.id for f in fits}
    per_job = collections.defaultdict(lambda: collections.defaultdict(float))
    for s in spans:
        top = s
        while top.parent in by_id:
            top = by_id[top.parent]
        if top is not s and top.id in jobs:
            per_job[s.name][top.id] += s.seconds
    hs.say(f"slowest job: round {slow.round}, {slow.seconds * 1e3:.1f} ms "
           f"(median {statistics.median(f.seconds for f in fits) * 1e3:.1f})"
           + "".join(f"; {name.rsplit('.', 1)[1]} "
                     f"{of_job[slow.id] * 1e3:.1f} "
                     f"({statistics.median(of_job.values()) * 1e3:.1f})"
                     for name, of_job in sorted(per_job.items())))
    return slow.seconds * 1e3
