"""Traffic driver `train`: a trainer catching up on a log of fixed size,
closed loop — rounds (jobs) back to back for the window; a group that
reaches the log's end is sought back to its start, and the wrap counted.

Set-up builds the one trainer, hands it the seeded weights, and drives
it through its first round with the window's own call and feed; that
round is what the plain reference follows.  The window then goes on
with the same object.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import harness as hs


def log_spec(run) -> dict:
    """What process B fills the log with (no JAX is imported yet)."""
    dep, scale = run.cfg["deployment"], run.traffic["log_scale"]
    cars = max(int(dep["cars"] * scale), 100)
    return {"arrivals": "backlog", "cars": cars,
            "ticks": math.ceil(dep["log_records"] * scale / cars)}


def run(run) -> dict:
    import jax

    job, dep = run.cfg["job"], run.cfg["deployment"]
    cars, ticks = run.log_spec["cars"], run.log_spec["ticks"]
    ready = run.connect_log()
    ends = dict(enumerate(ready["ends"]))
    hs.say(f"log: {sum(ends.values())} records on {len(ends)} partitions, "
           f"filled in {ready['fill_s']:.2f} s, fsync={ready['fsync']}")

    run.lap("log up")
    trainer = run.adapter.Trainer(run)
    run.lap("trainer built")
    window = job.get("window")
    rows_per_round = job["batch_size"] * job["take_batches"]
    rec = hs.Recorder(trainer.batcher(), lambda i: i < job["take_batches"])
    trainer.set_batcher(rec)
    params0 = run.adapter.init_params(run.seed)
    run.lap("weights made")
    shape = (job["batch_size"],) + ((window,) if window else ()) + (18,)
    trainer.seed_weights(params0, np.zeros(shape, np.float32))
    params0 = jax.device_get(params0)
    trainer.wrap(run.spans.wrap)
    wrapper = hs.Wrapper(trainer.consumer, dep["topic"], ends,
                         trainer.min_available)
    consumed0 = hs.registry().get("iotml_records_consumed_total", 0.0)
    committed0 = {p: run.broker.committed(trainer.group, dep["topic"], p)
                  for p in ends}

    run.lap("trainer built, weights seeded")
    rounds = []
    trainer.run(stop=lambda: bool(rounds), on_round=rounds.append)
    run.lap("first round done: window opens")
    first = rounds[0]
    first_losses = list(trainer.last_losses)
    first_state = trainer.state()
    first_pos = hs.positions(trainer.consumer)
    rec.keep = None
    if run.on_chip() and first["interpret"]:
        raise SystemExit("the fit ran under the Pallas interpreter on a "
                         "chip: not a measurement")

    # ------------------------------------------------------ the window
    tracer = hs.TraceWindow(run, run.traffic["trace_seconds"])
    setup_s = run.setup_done()
    reg0, spans0 = hs.registry(), run.spans.snapshot()
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    n0 = len(rounds)

    def stop() -> bool:
        now = time.perf_counter()
        if now >= t_end:
            return True
        tracer.maybe_start(now, t_end)
        wrapper.wrap_if_short()
        return False

    stamps = [t0]

    def on_round(stats) -> None:
        rounds.append(stats)
        stamps.append(time.perf_counter())

    trainer.run(stop=stop, on_round=on_round)
    elapsed = time.perf_counter() - t0
    tracer.close_window()
    reg1, spans1 = hs.registry(), run.spans.snapshot()
    traced = tracer.stop()
    device = hs.device_report(run, traced)

    in_window = rounds[n0:]
    records = sum(r["records"] for r in in_window)
    short = [r for r in in_window if r["records"] != rows_per_round]
    hs.say(f"window: {len(in_window)} rounds, {records} records in "
           f"{elapsed:.3f} s, {wrapper.wraps} wraps, fit={first['fit']} "
           f"interpret={first['interpret']}")
    took = np.diff(stamps)
    hs.say(f"rounds: {took.min():.4f} / {np.median(took):.4f} / "
           f"{took.max():.4f} s (least, median, most)")
    run.notes.update(registry=hs.delta(reg1, reg0),
                     spans=hs.delta(spans1, spans0),
                     rounds=len(in_window), traced=traced)

    # ---------------------------------------------------- bookkeeping
    t_check = time.perf_counter()
    pos = hs.positions(trainer.consumer)
    committed = {p: run.broker.committed(trainer.group, dep["topic"], p)
                 for p in ends}
    # a trainer whose jobs store nothing commits nothing: its group's
    # offsets have to stand where they stood
    due = pos if getattr(trainer, "commits", True) else committed0
    consumed = reg1.get("iotml_records_consumed_total", 0.0) - consumed0
    moved = sum(pos.values()) + wrapper.rewound
    run.check("rounds_short", len(short) + int(
        any(r["records"] != rows_per_round for r in rounds[:n0])), 0, True)
    run.check("offsets_moved_minus_records_consumed",
              int(moved - consumed), 0, True)
    run.check("committed_minus_position", sum(
        abs((committed[p] or 0) - (due[p] or 0)) for p in ends), 0, True)
    run.check("artifacts_missing",
              0 if trainer.artifacts_ok(len(rounds)) else 1, 0, True)

    # ------------------------------ numerics, against the plain reference
    xs = np.stack([k[2] for k in rec.kept])
    ys = xs if rec.kept[0][3] is None else np.stack(
        [k[3] for k in rec.kept])
    masks = np.stack([(np.arange(job["batch_size"]) < k[1])
                      .astype(np.float32) for k in rec.kept])
    need = max(math.ceil(first_pos[p] / max(ends[p] / ticks, 1))
               for p in ends)
    ref = hs.reference_rows(run, min(need + 1, ticks), cars)
    col = run.traffic["match_field"]
    # x holds `window` rows a window, y the one row after it
    flat_x, flat_y = xs.reshape(-1, 18), ys.reshape(-1, 18)
    valid_x = np.repeat(masks.reshape(-1) > 0, window or 1)
    valid_y = valid_x if ys is xs else masks.reshape(-1) > 0
    ix, gap_x = hs.match_rows(flat_x[valid_x], ref, col)
    iy, gap_y = hs.match_rows(flat_y[valid_y], ref, col)
    run.check("input_row_gap", float(max(gap_x.max(), gap_y.max())),
              run.cfg["limits"]["train"]["input_row_gap"])
    rx, ry = np.zeros_like(flat_x), np.zeros_like(flat_y)
    rx[valid_x], ry[valid_y] = ref[ix], ref[iy]
    hs.say(f"check +{time.perf_counter() - t_check:6.2f} s  rows matched")
    numbers = compare(run, params0, rx.reshape(xs.shape),
                      ry.reshape(ys.shape), masks, first_losses,
                      first_state)
    hs.say(f"check +{time.perf_counter() - t_check:6.2f} s  compared")
    for name, value in numbers.items():
        # a configuration holds the numbers its file gives a limit
        if name in run.cfg["limits"]["train"]:
            run.check(name, value, run.cfg["limits"]["train"][name])
        else:
            hs.say(f"read  {name}: {value!r} (no limit in this "
                   f"configuration)")

    return {"attempted": len(in_window), "failed": len(short),
            "device": device,
            "end_to_end": {"train_records_per_s": records / elapsed,
                           # every position of every window, every epoch
                           "train_tokens_per_s": records * (window or 1)
                           * job["epochs"] / elapsed,
                           "setup_s": setup_s}}


def compare(run, params0, xs, ys, masks, losses, state, dtype=None) -> dict:
    """The first round by the plain reference, and the numbers compared.
    `dtype` set: the reference itself in that lower precision stands in
    the program's place (the control) and `losses`/`state` are unused."""
    import jax
    import jax.numpy as jnp

    job = run.cfg["job"]
    fit = run.adapter.make_fit(run.adapter.loss_fn, job["epochs"])
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = jax.device_get(fit(params0, xs, ys, masks))
        if dtype is not None:
            cast = lambda t: jax.tree.map(  # noqa: E731
                lambda a: jnp.asarray(a, dtype), t)
            low = jax.device_get(fit(cast(params0), cast(xs), cast(ys),
                                     cast(masks)))
            state, losses = low[:3], np.asarray(low[3], np.float64)
    hs.say(f"reference: {time.perf_counter() - t_ref:.2f} s")
    p_ref, _mu_ref, nu_ref, l_ref = ref
    l_ref = np.asarray(l_ref, np.float64)
    losses = np.asarray(losses, np.float64)
    gaps = np.abs(losses - l_ref) / np.abs(l_ref)
    hs.say("epoch losses, reference:", [float(f"{v:.6g}") for v in l_ref])
    hs.say("epoch loss gaps:", [float(f"{v:.3g}") for v in gaps])
    p, _mu, nu = state
    # a 20-epoch round can hold a phase change of training (a unit
    # waking up); the two sides then pass it an epoch apart and single
    # leaves differ by tens of per cent for the rest of the round, so
    # the norms are compared over all leaves as one vector and the worst
    # leaf is printed beside them
    update, update_leaf = hs.norm_gaps(hs.tree_sub(p, params0),
                                       hs.tree_sub(p_ref, params0))
    moment, moment_leaf = hs.norm_gaps(nu, nu_ref)
    hs.say(f"by the worst leaf: update_norm_gap {update_leaf:.4g}, "
           f"moment_norm_gap {moment_leaf:.4g}")
    return {"epoch_loss_gap": float(gaps[:3].max()),
            "moment_norm_gap": moment, "update_norm_gap": update,
            "moment_leaf_gap": moment_leaf, "update_leaf_gap": update_leaf}
