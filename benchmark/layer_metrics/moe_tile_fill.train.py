"""moe_tile_fill.train: of the rows the expert layers' dispatch walked in the window's jobs (iotml_moe_tile_rows_total: every live tile's rows), the share that held an assignment; the rest is the padding that fills each held expert's last tile."""

ROWS = 'iotml_moe_tile_rows_total{kind="%s"}'


def read(run):
    moved = run.notes.get("registry", {})
    live, padding = moved.get(ROWS % "live"), moved.get(ROWS % "padding")
    # nothing to read: a program without the counter (the parent's), a
    # configuration without expert layers, a window in which no tile ran
    if live is None or padding is None or not live + padding:
        return None
    return 100.0 * live / (live + padding)
