"""The benchmark's own car fleet: seeded sensor values and their wire form.

A copy, taken on purpose, of what `iotml/gen/simulator.py` and
`iotml/ops/avro.py` do for the KSQL car schema (per-car latent state,
correlated sensors, three failure modes, Confluent-framed Avro) — later
PRs may change the program and may not change the traffic, so nothing
here imports `iotml`.  Deterministic in the seed: the log child encodes
the records, and the chip-owning process regenerates the same values to
check what the program decoded and predicted.

Cars keep the reference's one message per `interval_s` with a uniform
random phase, so a tick's records are ordered by phase and record i of
tick k is due `(k + phase_i) * interval_s` after the stream starts.
"""

from __future__ import annotations

import numpy as np

#: the 18 sensor fields in schema order; True marks the Avro `int` fields
#: (KSQL widens the floats to `double`), the rest is the label
INT_FIELD = (False,) * 9 + (True,) * 4 + (False,) * 4 + (True,)
N_SENSORS = len(INT_FIELD)
FRAME = bytes([0, 0, 0, 0, 1])  # Confluent magic 0 + schema id 1
RECORD_BYTES = len(FRAME) + 13 * 9 + 4 * 2 + 3 + 2 + 5  # label "false"


class Fleet:
    """`num_cars` cars stepped one tick (one message a car) at a time."""

    def __init__(self, seed: int, num_cars: int, failure_rate: float = 0.01,
                 interval_s: float = 10.0):
        rng = np.random.default_rng(seed)
        n = num_cars
        self.rng = rng
        self.num_cars = n
        self.interval_s = interval_s
        self.speed = rng.uniform(0.0, 30.0, n)
        self.battery = rng.uniform(40.0, 100.0, n)
        self.firmware = rng.choice([1000, 2000], n).astype(np.int32)
        self.tire_base = rng.uniform(28.0, 33.0, (n, 4))
        self.failing = np.full(n, -1, np.int32)
        fail = rng.random(n) < failure_rate
        self.failing[fail] = rng.integers(0, 3, fail.sum())
        # publication order within every tick: by phase
        phase = rng.random(n)
        self.order = np.argsort(phase, kind="stable")
        self.phase = phase[self.order]
        self.tick = 0

    def due_s(self, tick: int) -> np.ndarray:
        """Seconds after stream start at which tick's records are due."""
        return (tick + self.phase) * self.interval_s

    def step(self):
        """Advance one tick; returns (raw [n,18] float64 in publication
        order, failing [n] bool, car index [n])."""
        n, rng = self.num_cars, self.rng
        self.speed = np.clip(
            self.speed + rng.normal(0, 2.0, n) - 0.02 * (self.speed - 20.0),
            0.0, 50.0)
        speed = self.speed
        throttle = np.clip(speed / 50.0 + rng.normal(0, 0.05, n), 0.0, 1.0)
        vibration = speed * rng.uniform(100.0, 150.0, n)
        self.battery = np.clip(self.battery - rng.uniform(0, 0.05, n),
                               0.0, 100.0)
        current = 5.0 + speed * 0.5 + rng.normal(0, 1.0, n)
        coolant = 20.0 + speed * 0.6 + rng.normal(0, 2.0, n)
        airflow = speed * 3.0 + rng.normal(0, 5.0, n)
        voltage = 200.0 + self.battery * 0.5 + rng.normal(0, 2.0, n)
        tires = self.tire_base + rng.normal(0, 0.5, (n, 4))
        accel = np.abs(rng.normal(0.5, 0.8, (n, 4)))
        intake = rng.uniform(15.0, 40.0, n)
        m0 = self.failing == 0  # engine failure: vibration spike
        vibration[m0] *= rng.uniform(2.0, 4.0, m0.sum())
        m1 = self.failing == 1  # tire blowout
        tires[m1, 0] = rng.uniform(10.0, 18.0, m1.sum())
        m2 = self.failing == 2  # battery fault: voltage sag, current spike
        voltage[m2] -= rng.uniform(30.0, 60.0, m2.sum())
        current[m2] *= rng.uniform(1.5, 3.0, m2.sum())
        f32 = lambda a: a.astype(np.float32).astype(np.float64)  # noqa: E731
        i32 = lambda a: a.astype(np.int32).astype(np.float64)  # noqa: E731
        raw = np.stack(
            [f32(coolant), f32(intake), f32(np.clip(airflow, 0, None)),
             f32(self.battery), f32(voltage), f32(np.clip(current, 0, None)),
             f32(speed), f32(vibration), f32(throttle)]
            + [i32(tires[:, k]) for k in range(4)]
            + [f32(accel[:, k]) for k in range(4)]
            + [self.firmware.astype(np.float64)], axis=1)
        self.tick += 1
        o = self.order
        return raw[o], (self.failing >= 0)[o], o


def car_key(i: int) -> bytes:
    return b"electric-vehicle-%05d" % i


def encode(raw: np.ndarray, failing: np.ndarray) -> list:
    """[n,18] raw values + labels → n Confluent-framed Avro records of
    the KSQL schema (19 nullable fields, every one present)."""
    n = len(raw)
    out = np.zeros((n, RECORD_BYTES), np.uint8)
    out[:, :5] = np.frombuffer(FRAME, np.uint8)
    pos = 5
    for j in range(N_SENSORS):
        out[:, pos] = 2  # union branch 1, zigzag
        pos += 1
        if not INT_FIELD[j]:
            out[:, pos:pos + 8] = raw[:, j].astype("<f8").view(
                np.uint8).reshape(n, 8)
            pos += 8
            continue
        z = raw[:, j].astype(np.int64) * 2  # zigzag of a non-negative int
        if j == N_SENSORS - 1:  # firmware: two varint bytes
            if z.min() < 128 or z.max() >= 16384:
                raise ValueError("firmware outside the two-byte varint")
            out[:, pos] = (z & 0x7F) | 0x80
            out[:, pos + 1] = z >> 7
            pos += 2
        else:
            if z.min() < 0 or z.max() >= 128:
                raise ValueError("tire pressure outside the one-byte varint")
            out[:, pos] = z
            pos += 1
    out[:, pos] = 2
    false_tail = np.frombuffer(b"\x0afalse", np.uint8)
    true_tail = np.frombuffer(b"\x08true\x00", np.uint8)
    out[:, pos + 1:] = np.where(failing[:, None], true_tail, false_tail)
    blob = out.tobytes()
    lens = RECORD_BYTES - failing.astype(np.int64)
    return [blob[i * RECORD_BYTES: i * RECORD_BYTES + ln]
            for i, ln in enumerate(lens.tolist())]


def normalize(raw: np.ndarray, ranges: list) -> np.ndarray:
    """(x - lo) / (hi - lo) * 2 - 1 per field, float32; a field whose
    range is null is zeroed (the reference's normalize_fn TODOs)."""
    out = np.zeros(raw.shape, np.float64)
    for j, r in enumerate(ranges):
        if r is not None:
            lo, hi = r
            out[:, j] = (raw[:, j] - lo) / (hi - lo) * 2.0 - 1.0
    return out.astype(np.float32)
