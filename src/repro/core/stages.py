"""Stable names of the stages of a training step.

Each stage wraps its work in ``jax.named_scope("stage.<name>")``
(:func:`scope`).  The scope only adds ``op_name`` metadata while the
step is traced: it changes no number, costs nothing when the step runs
and leaves fusion as it was.  A profiler trace of the step then names
each device op by the HLO instruction whose ``op_name`` holds the
stage, so device time can be read per stage.

An op belongs to the OUTERMOST ``stage.*`` on its ``op_name`` path:
the anchor's own forward/backward pass and relayouts count under
``anchor``, not under ``grads``.  Ops under no stage (the step's
``while`` and ``conditional`` themselves, the participation mask, the
loss metrics) are unattributed.

  grads          forward and backward pass of the voters (per_device_grads)
  correction     pre-sign DC / scaffold / mtgc correction, folded or broadcast
  relayout       moves between the parameter tree and the flat buffer
  sign_pack      sign and 1-bit pack of the pre-sign direction
  vote_exchange  the gather of sign words or tallies between chips
  vote_update    majority vote and v <- v - mu * vote
  tally_acc      one client's signs folded into the streamed tally
  tally_finish   the streamed tally's deferred threshold and update
  cloud_mean     the cloud's weighted mean of the edge models
  anchor         the round boundary's anchor / control-variate pass
"""
from __future__ import annotations

import functools

import jax

PREFIX = "stage."
STAGES = ("grads", "correction", "relayout", "sign_pack", "vote_exchange",
          "vote_update", "tally_acc", "tally_finish", "cloud_mean", "anchor")


def scope(name: str):
    """The named scope of stage ``name`` (one of :data:`STAGES`)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r} (one of {STAGES})")
    return jax.named_scope(PREFIX + name)


def in_stage(name: str):
    """Decorator: run the function under :func:`scope` ``(name)``.  A
    fresh scope per call, so recursive calls nest correctly (one
    ``named_scope`` object is not reentrant)."""
    scope(name)                                  # check the name now

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return run
    return wrap
