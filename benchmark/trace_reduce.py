"""From a profiler trace (`*.xplane.pb`) to the numbers the result line
carries: the seconds in which an operation ran on the device, the
operations that took most of them, and the idle gaps by what the host
was doing (the benchmark's own `bench.*` spans).

Device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one
event per executed operation.  Host spans are the `bench.*` events of
the `/host:CPU` plane.  The device's clock and the host's differ by a
millisecond or so in these traces, so a gap shorter than that is not
attributed to a span but summed as "(between device operations)".
"""

from __future__ import annotations

import collections

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SHORT_GAP_NS = 1_000_000
BETWEEN = "(between device operations)"
OUTSIDE = "(outside the benchmark's spans)"
TOP = 10


def union_ns(intervals) -> tuple:
    """Total length of the union of (start, end) pairs, and the merged
    pairs themselves."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _innermost(spans, a, b) -> dict:
    """Split [a, b) among the innermost spans that cover its pieces."""
    cover = [(s, e, n) for s, e, n in spans if s < b and e > a]
    cuts = sorted({a, b} | {x for s, e, _ in cover for x in (s, e)
                            if a < x < b})
    out = collections.defaultdict(float)
    for x, y in zip(cuts, cuts[1:]):
        mid = (x + y) / 2
        inside = [(s, n) for s, e, n in cover if s <= mid < e]
        out[max(inside)[1] if inside else OUTSIDE] += y - x
    return out


def reduce(planes) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])].  The window is the host's `bench.window` span
    where the trace has one, else the trace's own extent."""
    device_ops, host_spans, last, first = {}, [], 0.0, float("inf")
    for pname, lines in planes:
        for lname, events in lines:
            for name, start, dur in events:
                last = max(last, start + dur)
                first = min(first, start)
            if pname.startswith(DEVICE_PLANE) and lname == OPS_LINE:
                device_ops[pname] = events
            elif pname == HOST_PLANE:
                host_spans += [(s, s + d, n) for n, s, d in events
                               if n.startswith(SPAN_PREFIX)]
    if not device_ops:
        raise ValueError("the trace holds no device plane with an "
                         f"'{OPS_LINE}' line")
    w0, w1 = next(((s, e) for s, e, n in host_spans if n == WINDOW_SPAN),
                  (first, last))
    host_spans = [sp for sp in host_spans if sp[2] != WINDOW_SPAN]
    busy, by_op, gaps = [], collections.defaultdict(float), \
        collections.defaultdict(float)
    for events in device_ops.values():
        ivs = [(max(s, w0), min(s + d, w1)) for _n, s, d in events
               if s + d > w0 and s < w1]
        total, merged = union_ns(ivs)
        busy.append(total)
        for name, s, d in events:
            if s + d > w0 and s < w1:
                by_op[name[:120]] += (min(s + d, w1) - max(s, w0)) \
                    / len(device_ops)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            if b - a < SHORT_GAP_NS:
                gaps[BETWEEN] += (b - a) / len(device_ops)
                continue
            for name, ns in _innermost(host_spans, a, b).items():
                gaps[name] += ns / len(device_ops)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (w1 - w0) / 1e9,
            "device_ops": top(by_op), "idle_gaps": top(gaps)}


def read_planes(path: str) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                 for ev in ln.events])
                      for ln in p.lines if ln.name == OPS_LINE
                      or p.name == HOST_PLANE])
            for p in data.planes
            if p.name.startswith(DEVICE_PLANE) or p.name == HOST_PLANE]


def reduce_file(path: str) -> dict:
    return reduce(read_planes(path))
