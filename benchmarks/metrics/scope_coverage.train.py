"""kernels: the share of the training step's device time that sits under
a named scope (PR 36), as ``scope_coverage.serve`` over the traced
``jit_step`` programs.  The table comes from the trace's own file: each
operation's ``op_name`` is the ``tf_op`` stat of its event's metadata
(``lib/parts.train_row`` says how the file is found and read).  An
earlier line holds the whole table and the three largest operations
under no scope.  Nothing where the run has no device trace or no
operation of the step sits under a scope (a tree from before them)."""

from benchmarks.lib import parts


def read(run):
    row = parts.train_row(run)
    if row is None:
        return None
    parts.log_row(run, "scope_coverage.train", run["step_module_prefix"],
                  row)
    return parts.coverage_percent([row])
