"""Traffic driver `score`: the live scorer over the Kafka wire, either
draining a log of fixed size closed loop (`arrivals: backlog`, sought
back to the start at the end, wraps counted) or under an open loop that
never waits for it (`arrivals: open_loop`: process B publishes each
record when it is due; cars keep one message per interval with a uniform
phase, so the number of cars sets the rate).

The scorer serves weights the benchmark made from the seed: glorot
weights after one reference round on the stream's first records,
published as an artifact and loaded the way a user's scorer loads one.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import harness as hs


def log_spec(run) -> dict:
    """What the children make (no JAX is imported yet)."""
    dep, tr = run.cfg["deployment"], run.traffic
    if tr["arrivals"] == "open_loop":
        cars = int(tr["rate_per_s"] * dep["interval_s"])
        # the scorer looks up between drains only, and under a steady
        # stream a drain ends at its row limit: the window opens and
        # closes up to one drain late
        drain_s = run.cfg["scorer"]["max_rows_per_drain"] / tr["rate_per_s"]
        horizon = tr["lead_s"] + run.seconds + tr["grace_s"] + 3.0 \
            + 2 * drain_s
        ticks = math.ceil(horizon / dep["interval_s"]) + 1
    else:
        cars = max(int(dep["cars"] * tr["log_scale"]), 100)
        ticks = math.ceil(dep["log_records"] * tr["log_scale"] / cars)
    return {"arrivals": tr["arrivals"], "cars": cars, "ticks": ticks,
            "burst": tr.get("burst")}


def served_weights(run, cars: int):
    """Seeded weights after one reference job on the stream's head."""
    import jax

    job = run.cfg["job"]
    rows = hs.reference_rows(run, 1, cars)
    n = min(job["take_batches"], len(rows) // job["batch_size"])
    xs = rows[:n * job["batch_size"]].reshape(n, job["batch_size"], 18)
    fit = run.adapter.make_fit(run.adapter.loss_fn, job["epochs"])
    with jax.default_matmul_precision("highest"):
        return fit(run.adapter.init_params(run.seed), xs, xs,
                   np.ones(xs.shape[:2], np.float32))[0]


def run(run) -> dict:
    dep, tr = run.cfg["deployment"], run.traffic
    live = tr["arrivals"] == "open_loop"
    cars, ticks = run.log_spec["cars"], run.log_spec["ticks"]
    ready = run.connect_log()
    ends = dict(enumerate(ready["ends"]))
    hs.say(f"log: {sum(ends.values())} records, {cars} cars, {ticks} ticks "
           f"made in {ready['fill_s']:.2f} s, fsync={ready['fsync']}")

    run.lap("log up")
    weights = served_weights(run, cars)
    run.lap("weights made")
    out_topic = dep["predictions_topic"]
    scorer = run.adapter.Scorer(run, weights)
    run.lap("scorer built, model loaded, eval buckets warm")
    rec = hs.Reservoir(run.seed, tr["check_batches"]).attach(
        hs.Recorder(scorer.batcher(), None))
    scorer.set_batcher(rec)
    scorer.wrap(run.spans.wrap)
    base = run.broker.end_offset(out_topic, 0)
    consumed0 = hs.registry().get("iotml_records_consumed_total", 0.0)
    wrapper = hs.Wrapper(scorer.consumer, dep["topic"], ends, 0)

    def on_drain(_stats) -> None:
        """`cli.live` always hands the loop a reporter: so does this."""

    if live:
        t_live = run.log.ask("live_start")["t0"]
    else:  # one bounded drain: every lazy piece of the path has run
        scorer.run(stop=lambda: scorer.scored() > 0, on_drain=on_drain)

    # ------------------------------------------------------ the window
    tracer = hs.TraceWindow(run, tr["trace_seconds"])
    w = {"t0": None, "last_scored": -1}

    def open_window() -> None:
        if live:
            run.log.ask("mark", name="window_start")
        run.lap("first drains done: window opens")
        w["setup_s"] = run.setup_done()
        w["reg0"], w["spans0"] = hs.registry(), run.spans.snapshot()
        w["scored0"] = scorer.scored()
        w["t0"] = time.perf_counter()
        w["t_end"] = w["t0"] + run.seconds

    def stop() -> bool:
        now = time.perf_counter()
        if w["t0"] is None:
            if live and time.time() < t_live + tr["lead_s"]:
                return False
            open_window()
            return False
        if now >= w["t_end"]:
            return True
        tracer.maybe_start(now, w["t_end"])
        if not live:
            if scorer.scored() == w["last_scored"]:
                wrapper.wrap_if_short()  # caught up, and the drain is over
            w["last_scored"] = scorer.scored()
        return False

    scorer.run(stop=stop, on_drain=on_drain)
    elapsed = time.perf_counter() - w["t0"]
    if live:
        run.log.ask("mark", name="window_end")
    scored_in_window = scorer.scored() - w["scored0"]
    tracer.close_window()
    reg1, spans1 = hs.registry(), run.spans.snapshot()

    # -------------------- after the window: finish what is in flight
    child = {}
    if live:
        t_grace = time.time() + tr["grace_s"]
        scorer.run(stop=lambda: time.time() >= t_grace, on_drain=on_drain)
        sent = run.log.ask("live_stop")["sent"]
        scorer.finish_drain()
        child = run.log.ask("live_result", grace_s=tr["grace_s"])
        hs.say("open loop:", child)
        moved = sum(hs.positions(scorer.consumer).values())
        run.check("published_minus_consumed", sent - moved, 0, True)
    else:
        moved = sum(hs.positions(scorer.consumer).values()) \
            + wrapper.rewound
        for p, end in ends.items():  # leave the rest of the log unread
            scorer.consumer.seek(dep["topic"], p, end)
        scorer.finish_drain()
    traced = tracer.stop()
    device = hs.device_report(run, traced)
    hs.say(f"window: {scored_in_window} records scored in {elapsed:.3f} s, "
           f"{wrapper.wraps} wraps")
    run.notes.update(
        registry=hs.delta(reg1, w["reg0"]),
        spans=hs.delta(spans1, w["spans0"]), traced=traced, child=child)

    # ---------------------------------------------------- bookkeeping
    pos = hs.positions(scorer.consumer)
    committed = {p: run.broker.committed(scorer.group, dep["topic"], p)
                 for p in ends}
    consumed = hs.registry().get("iotml_records_consumed_total", 0.0) \
        - consumed0
    predictions = run.broker.end_offset(out_topic, 0) - base
    run.check("offsets_moved_minus_records_consumed",
              int(moved - consumed), 0, True)
    run.check("predictions_minus_records_consumed",
              int(predictions - consumed), 0, True)
    run.check("predictions_minus_rows_scored",
              int(predictions - scorer.scored()), 0, True)
    run.check("committed_minus_position", sum(
        abs((committed[p] or 0) - pos[p]) for p in ends), 0, True)

    # ------------------------------ numerics, against the plain reference
    xs = np.concatenate([k[2][:k[1]] for k in rec.kept])
    preds = []
    for before, n_valid, _x, _y in rec.kept:
        msgs = run.broker.fetch(out_topic, 0, base + before,
                                max_messages=n_valid)
        if len(msgs) < n_valid:
            raise RuntimeError(f"predictions {base + before}+{n_valid} "
                               f"are not all on the topic")
        preds += [run.adapter.Scorer.parse(m.value) for m in msgs[:n_valid]]
    preds = np.stack(preds)
    ref = hs.reference_rows(run, ticks, cars)
    ix, gap = hs.match_rows(xs, ref, tr["match_field"])
    limits = run.cfg["limits"]["score"]
    run.check("input_row_gap", float(gap.max()), limits["input_row_gap"])
    numbers = compare(run, weights, ref[ix], preds)
    hs.say(f"sample: {len(preds)} predictions of {len(rec.kept)} "
           f"batches;", numbers)
    run.check("mean_abs_gap", numbers["mean_abs_gap"],
              limits["mean_abs_gap"])

    if live:
        e2e = {"predict_latency_p50_ms": child["latency_p50_ms"],
               "predict_latency_p95_ms": child["latency_p95_ms"]}
        attempted, failed = child["offered"], child["failed"]
    else:
        e2e = {"score_records_per_s": scored_in_window / elapsed}
        attempted, failed = scored_in_window, 0
    return {"attempted": attempted, "failed": failed, "device": device,
            "end_to_end": dict(e2e, setup_s=w["setup_s"])}


def compare(run, weights, rows, preds, dtype=None) -> dict:
    """The reference's forward pass over `rows`, in the arithmetic the
    configuration states for this backend, against `preds`.  `dtype`
    set: the reference in that lower precision stands in for `preds`."""
    import jax
    import jax.numpy as jnp

    operands = jnp.bfloat16 if run.on_chip() else None
    fwd = jax.jit(run.adapter.forward, static_argnames="operands")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(fwd(weights, rows, operands=operands), np.float64)
        exact = np.asarray(fwd(weights, rows), np.float64)
        if dtype is not None:
            cast = lambda t: jax.tree.map(  # noqa: E731
                lambda a: jnp.asarray(a, dtype), t)
            preds = np.asarray(fwd(cast(weights), cast(rows)).astype(
                jnp.float32), np.float64)
    d = np.abs(preds - ref)
    return {"mean_abs_gap": float(d.mean()), "max_abs_gap": float(d.max()),
            "mean_abs_gap_to_highest": float(np.abs(preds - exact).mean())}
