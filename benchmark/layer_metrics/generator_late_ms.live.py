"""generator_late_ms.live: 95th percentile of (published - due) in process B over the window's records."""


def read(run):
    return run.notes.get("child", {}).get("late_p95_ms")
