"""model_step: what ``jax.checkpoint`` makes a training step compute a
second time (PR 36): device time of every operation whose ``op_name``
sits under ``rematted_computation``, whatever its part, per ``jit_step``
program of the traced steps (``lib/parts.train_row``); an earlier line
gives it by part.  Nothing where the run has no device trace or the
step carries no scope."""

from benchmarks.lib import parts


def read(run):
    row = parts.train_row(run)
    if row is None:
        return None
    run["log"]("recomputed_forward_ms: by part " + str({
        part: round(ms, 3) for (part, phase), ms in row["parts"].items()
        if phase == "recomputed"}))
    return parts.part_ms(row, phase="recomputed")
