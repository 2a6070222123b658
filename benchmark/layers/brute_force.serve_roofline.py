"""Served exact-search batches' least time (one full read of the
corpus each, and the valid rows' dot products) over the device's busy
time in the window, in %."""

from benchmark.roofline import share as read  # noqa: F401
