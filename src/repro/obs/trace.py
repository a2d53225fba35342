"""The ``--profile`` hook: a ``jax.profiler`` trace around a run.

The trace is the device's and the host's own record, on one clock: device
op times carry the train step's ``jax.named_scope`` layers and the host
lines carry ``Trainer.run``'s spans (names in ``repro.obs.scopes``). Read
it with ``jax.profiler.ProfileData``, TensorBoard or Perfetto.
"""
from __future__ import annotations

import contextlib
import os

__all__ = ["profiler_session"]


@contextlib.contextmanager
def profiler_session(enabled: bool, logdir: str):
    """``--profile`` hook: jax.profiler trace around the run when enabled."""
    if not enabled:
        yield None
        return
    import jax
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
