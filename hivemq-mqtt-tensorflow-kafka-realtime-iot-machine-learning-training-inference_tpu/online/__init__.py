"""iotml.online — true online learning with drift-triggered adaptation.

The reference is explicit that it does micro-batch streaming ingestion,
*not* online learning (reference README.md:130-140).  This package goes
past it:

- ``OnlineLearner``: per-record/small-window SGD folded into the
  consume loop — every polled window is one fixed-shape jitted update,
  reusing ``ContinuousTrainer``'s cursor/commit discipline so
  offsets-as-checkpoint still holds;
- ``PageHinkley`` / ``AdaptiveWindow`` (ADWIN-style) streaming drift
  detectors over the reconstruction-error signal, composed by
  ``DriftMonitor`` into a STABLE → ADAPTING → STABLE state machine;
- drift-triggered adaptation (``AdaptationPolicy``): learning-rate
  boost, detector-window reset, or replay-buffer re-fit — each adapted
  model published through the ``iotml.mlops`` ``ModelRegistry`` so the
  scorer fleet hot-swaps it via the existing ``RegistryWatcher``, with
  the A/B rollback gate protecting against a bad adaptation.

Proof lives in ``iotml.online.drill`` (the live drift-adapt-swap
drill) and the ``drift-storm`` chaos scenario; online against
micro-batch throughput is not measured on the chip.  Lint rule R13 keeps model updates
flowing through the registry — no in-place ``set_params`` on a serving
scorer outside the mlops/online machinery.
"""

from .detectors import (ADAPTING, STABLE, AdaptiveWindow, DriftMonitor,
                        PageHinkley)
from .learner import AdaptationPolicy, OnlineLearner

__all__ = ["AdaptiveWindow", "AdaptationPolicy", "ADAPTING",
           "DriftMonitor", "OnlineLearner", "PageHinkley", "STABLE"]
