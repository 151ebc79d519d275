"""The three faults `kl-train-backlog`'s `correct` has to refuse, each
planted in the PROGRAM (the reference stays what it is), by hand:

    python3 benchmark/tests/planted_faults.py <fault> --workload \\
        kl-train-backlog --seed 5000000131 --seconds 5 --trace 0

is `benchmark/run.py` with one of them planted ahead of it — on the chip
at the cell's own size (the configuration's `limits_why` names the
number that refused each), and on the CPU at a tiny preset in
`test_delta_cell.py` and `tests/test_kimi_linear_stack.py`.

- `scalar_gate`: the decay averaged over a head's channels — a scalar
  gate a head, the sibling mechanism Kimi Delta Attention refines;
- `no_delta`: the delta correction dropped, `S_t = Diag(α_t) S_{t−1} +
  β_t k_t v_tᵀ` (gated linear attention) — through the rule's own
  operands: k shrunk and v grown by 1e3, so the write β k vᵀ stands and
  the correction β k kᵀ is 1e-6 of itself;
- `rope`: the latent layer turns its 64-wide parts, as
  `sensorformer-kimi-vl-a3b-instruct`'s does.
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAULTS = ("scalar_gate", "no_delta", "rope")


def plant(fault: str, setattr=setattr) -> None:
    """Plant `fault` in the imported program; `setattr` may be a
    `monkeypatch.setattr`, which takes it out again."""
    import jax.numpy as jnp

    from iotml.models import hybrid
    from iotml.ops import delta

    reads, scan = delta.rule_inputs, hybrid.kda_scan
    if fault == "scalar_gate":
        def averaged(*made):
            q, k, g = reads(*made)
            return q, k, jnp.broadcast_to(
                jnp.mean(g, axis=-1, keepdims=True), g.shape)
        setattr(delta, "rule_inputs", averaged)
    elif fault == "no_delta":
        def shrunk(*made):
            q, k, g = reads(*made)
            return q, 1e-3 * k, g
        setattr(delta, "rule_inputs", shrunk)
        setattr(hybrid, "kda_scan",
                lambda q, k, v, *rest: scan(q, k, 1e3 * v, *rest))
    elif fault == "rope":
        post = hybrid.SensorHybrid.__post_init__

        def turned(self):
            object.__setattr__(self, "cfg", dataclasses.replace(
                self.cfg, mla_rope=True))
            post(self)
        setattr(hybrid.SensorHybrid, "__post_init__", turned)
    else:
        raise SystemExit(f"no fault {fault!r}: one of {FAULTS}")


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    plant(sys.argv[1])
    from benchmark import run as bench_run

    raise SystemExit(bench_run.main(sys.argv[2:]))
