"""engine_host: mean of active slots over ``num_slots`` across the
window's steps, from the counts ``ServingMetrics.record_step`` keeps
(``batch_fill_ratio`` after a ``reset()`` at the window's start)."""


def read(run):
    if run.get("occupancy") is None:
        return None
    return 100.0 * run["occupancy"]
