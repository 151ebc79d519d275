"""lag_records.live: records published and not yet predicted, as process B counts them at each of its 10 ms watch samples; median over the window."""


def read(run):
    return run.notes.get("child", {}).get("lag_median")
