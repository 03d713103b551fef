"""model_step: median host time of a training step in the traced run,
each step closed by ``block_until_ready``."""

from benchmarks.lib import stats


def read(run):
    closed = run.get("closed_steps")
    return 1e3 * stats.median(closed) if closed else None
