"""Re-ranked IVF-RaBitQ search calls' least time over the device's busy
time in the window, in % (closed-loop cells: every call is a full
batch)."""

from benchmark.roofline import share as read  # noqa: F401
