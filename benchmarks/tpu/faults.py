"""Faults planted underneath the timed path, to show that ``correct``
catches them (tests/test_faults.py, and control.py on the chip). Each
``plant_<fault>()`` patches the program in this process and returns a
function that undoes it.

- ``half_batch``: half of each step's targets leave the loss, which
  becomes the mean over the rest;
- ``frozen_step``: the step hands its state back unchanged;
- ``altered_answer``: the server alters one logit of each answer where
  it produces it.
"""
from __future__ import annotations

import numpy as np


def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def _halve(mask):
    mask = np.array(mask, np.float32)
    flat = mask.reshape(-1)
    live = np.flatnonzero(flat)
    flat[live[::2]] = 0.0
    return mask


def plant_half_batch():
    import dataclasses
    from repro.core import trainer as tr
    undo = []
    prepare = tr.CompactTrainer._prepare

    def compact_prepare(self, view):
        block = prepare(self, view)
        return dataclasses.replace(block, loss_mask=_halve(block.loss_mask))

    undo.append(_patch(tr.CompactTrainer, "_prepare", compact_prepare))
    shard = tr.shard_view

    def shard_view(plan, view):
        out = dict(shard(plan, view))
        out["loss_mask"] = _halve(out["loss_mask"])
        return out

    undo.append(_patch(tr, "shard_view", shard_view))
    return lambda: [u() for u in reversed(undo)]


def plant_frozen_step():
    from repro.core import trainer as tr
    undo = []
    for cls in (tr.CompactTrainer, tr.Trainer):
        dispatch = cls._dispatch

        def frozen(self, staged, dispatch=dispatch):
            _, _, loss = dispatch(self, staged)
            return self.params, self.opt_state, loss

        undo.append(_patch(cls, "_dispatch", frozen))
    return lambda: [u() for u in reversed(undo)]


def plant_altered_answer():
    from repro.serving import server as sv
    serve = sv.GNNServer._serve_locked

    def altered(self, nodes):
        out = np.array(serve(self, nodes))
        out[:, 0] += 0.5
        return out

    return _patch(sv.GNNServer, "_serve_locked", altered)


FAULTS = {"half_batch": plant_half_batch, "frozen_step": plant_frozen_step,
          "altered_answer": plant_altered_answer}
