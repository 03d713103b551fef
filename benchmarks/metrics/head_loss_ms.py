"""model_step: what the vocabulary head and the cross-entropy cost a
training step (PR 36): device time under the scopes ``head`` and
``loss``, forward and backward (the head's activation and weight
gradients included), per ``jit_step`` program of the traced steps
(``lib/parts.train_row``); an earlier line gives it by part and phase.
Nothing where the run has no device trace or the step carries no
scope."""

from benchmarks.lib import parts


def read(run):
    row = parts.train_row(run)
    if row is None:
        return None
    run["log"]("head_loss_ms: " + str({
        f"{part}.{phase}": round(ms, 3)
        for (part, phase), ms in row["parts"].items()
        if part in ("head", "loss")}))
    return parts.part_ms(row, "head", "loss")
