"""The device a process owns, and where its compiled programs are kept.

A chip belongs to one process at a time, and JAX falls back to the CPU
without a word when it cannot start an accelerator.  Entry points that
own a device call `claim_device()` once, before any other JAX work: it
initialises the backend (the span `iotml.start.backend`), refuses a CPU
nobody asked for, places the persistent compile cache, starts counting
compilations (`iotml_compile_seconds`, `iotml_compile_cache_total`),
and returns what the process got so banners and stats lines can name
it; `compile_report()` is those counters for a readiness line.
`python -m iotml.utils.device` prints that report plus the installed
versions as one JSON line.
"""

from __future__ import annotations

import json
import os
from typing import Optional

#: the package lives one level below the checkout root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))


def enable_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns its directory,
    or None where this process keeps none.

    A cache that moves never hits, so the directory is fixed:
    `JAX_COMPILATION_CACHE_DIR` wins when set (JAX reads it itself — no
    directory is set in code), otherwise one path inside the checkout.
    A process pinned to the CPU gets no in-checkout cache: XLA:CPU
    executables are tied to the machine that compiled them (loading one
    elsewhere risks SIGILL, and jaxlib warns at length on every load
    even at home), they recompile in milliseconds, and a checkout
    travels between machines.

    The live path's programs compile in well under JAX's default 1 s
    persistence threshold (the fused round fit, the scorer's eval
    buckets), so the threshold drops to zero — a restarted service then
    loads every program instead of recompiling it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        # asked of the config, not the backend: callers that only host
        # the data plane must not start (and so take) a device here
        if jax.config.jax_platforms == "cpu":
            return None
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


#: JAX's monitoring events → `stage` of iotml_compile_seconds
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_RESULTS = {"/jax/compilation_cache/cache_hits": "hit",
                  "/jax/compilation_cache/cache_misses": "miss"}
_listening = False


def listen_for_compiles() -> None:
    """Book what JAX reports of its own compilations into
    `iotml_compile_seconds{stage,program}` and
    `iotml_compile_cache_total{result,program}`; registered once a
    process (JAX has no unregister).  `program` is the jitted function's
    name where it is one of the program's own (`iotml_*`,
    train/loop.py), else "other", so the label set is bounded.  JAX
    times a cache read, and reports a hit or a miss, without naming the
    program; each happens inside that program's `backend` event, on the
    same thread, so it is held until that event names it."""
    global _listening
    if _listening:
        return
    _listening = True
    import threading

    import jax.monitoring

    from ..obs import metrics as obs_metrics

    pending = threading.local()

    def program_of(kw) -> str:
        # "iotml_scanned_fit" when traced, "jit(iotml_scanned_fit)"
        # when lowered and compiled
        name = str(kw.get("fun_name", ""))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        return name if name.startswith("iotml_") else "other"

    def on_duration(event: str, seconds: float, **kw) -> None:
        if event == _CACHE_READ:
            pending.read_s = seconds
            return
        stage = _COMPILE_STAGES.get(event)
        if stage is None:
            return
        program = program_of(kw)
        obs_metrics.compile_seconds.observe(seconds, stage=stage,
                                            program=program)
        if stage != "backend":
            return
        read_s = getattr(pending, "read_s", None)
        if read_s is not None:
            pending.read_s = None
            obs_metrics.compile_seconds.observe(read_s, stage="cache_read",
                                                program=program)
        result = getattr(pending, "result", None)
        if result is not None:
            pending.result = None
            obs_metrics.compile_cache.inc(result=result, program=program)

    def on_event(event: str, **kw) -> None:
        result = _CACHE_RESULTS.get(event)
        if result is not None:
            pending.result = result

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def claim_device() -> dict:
    """Initialise this process's backend and report it.

    Raises RuntimeError when JAX fell back to the CPU although
    `JAX_PLATFORMS` did not name it: a service that believes it is on
    the chip must not run on the host unnoticed."""
    import jax

    from ..obs import tracing

    with tracing.phase("start", "backend"):
        devices = jax.devices()
    first = devices[0]
    if first.platform == "cpu" and \
            "cpu" not in (jax.config.jax_platforms or ""):
        raise RuntimeError(
            "JAX found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu to run on the host on purpose")
    listen_for_compiles()
    return {"platform": first.platform, "device_kind": first.device_kind,
            "count": len(devices),
            "compile_cache_dir": enable_compile_cache()}


def compile_report() -> dict:
    """What `listen_for_compiles()` has booked for the program's own
    programs so far: `seconds` by stage (`cache_read` is part of
    `backend`), `hit`, the programs the persistent cache served, and
    `missed`, those it did not hold — the ones a start compiled (a
    process without a cache, as one pinned to the CPU, names neither)."""
    import re

    from ..obs import metrics as obs_metrics

    seconds = dict.fromkeys(("trace", "lower", "backend", "cache_read"), 0.0)
    cached = {"hit": [], "miss": []}
    series = re.compile(r'iotml_compile_(seconds_sum|cache_total)'
                        r'\{program="(iotml_\w+)",(?:stage|result)="(\w+)"\}')
    for key, value in obs_metrics.default_registry.collect().items():
        found = series.fullmatch(key)
        if found is None:
            continue
        family, program, what = found.groups()
        if family == "seconds_sum":
            seconds[what] += value
        elif value:
            cached[what].append(program)
    return {"seconds": seconds, "hit": sorted(cached["hit"]),
            "missed": sorted(cached["miss"])}


def device_text(report: dict) -> str:
    """'4x TPU v5 lite (tpu)' — the banner form of a claim_device() report."""
    return (f"{report['count']}x {report['device_kind']} "
            f"({report['platform']})")


def versions() -> dict:
    """Installed jax / jaxlib / libtpu versions (libtpu None if absent)."""
    from importlib import metadata

    out = {}
    for name in ("jax", "jaxlib", "libtpu"):
        try:
            out[name] = metadata.version(name)
        except metadata.PackageNotFoundError:
            out[name] = None
    return out


if __name__ == "__main__":
    print(json.dumps({**claim_device(), "versions": versions()}), flush=True)
