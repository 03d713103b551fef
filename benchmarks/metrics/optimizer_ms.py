"""model_step: what the parameter update costs a training step (PR 36):
device time under the scope ``optimizer`` (``Optimizer.update``: clip,
schedule, every leaf's update behind its barrier) per ``jit_step``
program of the traced steps (``lib/parts.train_row``).  Nothing where
the run has no device trace or the step carries no scope."""

from benchmarks.lib import parts


def read(run):
    row = parts.train_row(run)
    return None if row is None else parts.part_ms(row, "optimizer")
