"""Checkpoint/resume: orbax model state + explicit stream cursors.

The reference's resume story (SURVEY §5) is two-part: the model moves as a
Keras h5 blob through GCS (cardata-v3.py:227-232, :255-261), and the *data
position* is the Kafka offset, passed as an absolute CLI argument.  Here both
halves live in one orbax checkpoint: params/opt-state/step plus the
`(topic, partition, next_offset)` cursor list from
`StreamConsumer.positions()`, so a restarted trainer resumes both model and
stream exactly where it stopped.

Crash safety (ISSUE 7 satellite): a save stages into a hidden temp
directory and is RENAMED into place (one atomic publication, parent dir
fsynced via the store's ``fsync_dir`` — durability promises live in one
package), so a kill mid-save can never leave a half-written ``step_*``
directory under the canonical name; ``restore()`` walks steps newest-
first and SKIPS a torn/corrupt checkpoint back to the newest intact one
instead of raising mid-unpickle.  For async + versioned + hot-swappable
checkpoints use ``iotml.mlops`` — this manager remains the minimal
single-trainer resume primitive.
"""

from __future__ import annotations

import os
import shutil
from typing import Optional

import jax
import numpy as np

from ..store import fsync_dir


class CheckpointManager:
    """Thin orbax wrapper: save/restore (state pytree, cursors)."""

    def __init__(self, directory: str):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        # imported where a checkpoint is first written or read, not with
        # the package: it pulls in google.cloud.logging, whose version
        # check scans every installed distribution — 12 s of every
        # start of a trainer on the chip's host (PERF.md §6, PR 34), and
        # a trainer that never checkpoints never needs it
        import orbax.checkpoint as ocp

        self._ckpt = ocp.PyTreeCheckpointer()
        #: torn/corrupt step dirs skipped by the last restore() walk
        self.skipped_torn = 0

    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def save(self, state, cursors=None, step: Optional[int] = None):
        step = int(state.step) if step is None else step
        payload = {
            "params": jax.device_get(state.params),
            "opt_state": jax.device_get(state.opt_state),
            "step": np.asarray(int(state.step)),
            "cursors": [list(c) for c in (cursors or [])],
        }
        final = self._path(step)
        # stage under a hidden name, publish by rename: readers (and
        # latest_step) can never observe a partially-written step dir,
        # and a kill mid-save leaves only a .tmp orphan save() reclaims
        tmp = os.path.join(self.directory, f".tmp_step_{step:010d}")
        shutil.rmtree(tmp, ignore_errors=True)
        self._ckpt.save(tmp, payload, force=True)
        shutil.rmtree(final, ignore_errors=True)
        os.replace(tmp, final)
        fsync_dir(self.directory)
        return final

    def steps(self) -> list:
        """Committed step ids, ascending (staged .tmp dirs excluded)."""
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_"):
                try:
                    steps.append(int(name.split("_")[1]))
                except ValueError:
                    continue
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.steps()
        return steps[-1] if steps else None

    def restore(self, step: Optional[int] = None) -> Optional[dict]:
        """Restore `step`, or the newest INTACT checkpoint.

        With no explicit step, a torn latest (pre-atomic-save legacy, a
        bit-rotted disk, manual surgery) is skipped — newest-first —
        back to the first checkpoint that loads, instead of raising
        mid-unpickle and bricking the resume path.  An explicit step
        still raises: the caller named it, silence would lie."""
        self.skipped_torn = 0
        if step is not None:
            return self._load(step)
        for s in reversed(self.steps()):
            try:
                return self._load(s)
            except Exception:  # noqa: BLE001 - any torn artifact
                # (truncated msgpack, missing leaf file, bad metadata)
                self.skipped_torn += 1
                continue
        return None

    def _load(self, step: int) -> dict:
        payload = self._ckpt.restore(self._path(step))
        payload["cursors"] = [tuple([c[0], int(c[1]), int(c[2])])
                              for c in payload.get("cursors", [])]
        return payload
