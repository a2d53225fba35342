"""Names of the train step's layers, as the profiler trace carries them.

Device scopes (``jax.named_scope``) go into each op's ``op_name`` metadata,
so a device trace attributes op time to a layer:

* ``FORWARD`` wraps the model loss. Differentiating it puts the backward
  ops under ``transpose(jvp(edgc.forward))`` (remat's recompute too), so
  the flat step needs no backward scope of its own.
* ``BACKWARD`` wraps the pipelined executor's hand-rolled backward tick
  (its ``jax.vjp`` recompute included).
* ``COMPRESS`` wraps the DP gradient sync: PowerSGD's factor products, the
  QR, error feedback, decompression and the psum-means between them.
* ``ENTROPY`` wraps the gradient entropy reading (GDS).
* ``OPTIMIZER`` wraps the Adam update (and, on the guarded path, the
  norm and the keep-or-skip selects).

Host spans (``jax.profiler`` annotations, on the trace's own clock) name
what ``Trainer.run`` was doing: ``STEP`` one loop iteration, ``FLUSH`` the
deferred-metric drain, ``WINDOW_END`` the DAC re-plan and the recompile it
triggers, ``CHECKPOINT`` a save.
"""
from __future__ import annotations

FORWARD = "edgc.forward"
BACKWARD = "edgc.backward"
COMPRESS = "edgc.compress"
ENTROPY = "edgc.entropy"
OPTIMIZER = "edgc.optimizer"
DEVICE_SCOPES = (FORWARD, BACKWARD, COMPRESS, ENTROPY, OPTIMIZER)

STEP = "edgc.step"
FLUSH = "edgc.flush"
WINDOW_END = "edgc.window_end"
CHECKPOINT = "edgc.checkpoint"
HOST_SPANS = (STEP, FLUSH, WINDOW_END, CHECKPOINT)
