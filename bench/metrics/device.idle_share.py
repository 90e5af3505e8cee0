"""Share of the traced window in which no op runs on the chip, on the chip
with the most idle, in percent."""

from harness import trace as TR


def read(run):
    lo, hi = run.trace.window
    busy = min(TR.busy_ns(ops, (lo, hi)) for ops in run.trace.devices.values())
    return 100.0 * (1.0 - busy / (hi - lo))
