"""Served refined IVF-PQ batches' least time, counting valid rows only
(padding lowers it), over the device's busy time in the window, in %."""

from benchmark.roofline import share as read  # noqa: F401
