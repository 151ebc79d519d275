"""recompiles.train: programs built or loaded inside the window (`iotml_compile_seconds_count{stage="backend"}`, every program): each is a stall of seconds in the stream; 0 in a warmed window."""


def read(run):
    counts = [v for k, v in run.notes.get("registry", {}).items()
              if k.startswith("iotml_compile_seconds_count")
              and 'stage="backend"' in k]
    # no such series at all: a program that does not count its compiles
    return float(sum(counts)) if counts else None
