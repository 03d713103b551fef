"""device: 1 - (union of the intervals in which an operation ran on
device 0) / the traced window, from the trace's ``XLA Ops`` line."""

from benchmarks.lib.xplane import device0_idle_percent as read  # noqa: F401
