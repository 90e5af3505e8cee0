"""Milliseconds per outer step of collective device time during which no
other op runs on the chip, on the chip where it is largest."""

from harness import trace as TR


def read(run):
    chips = [ops for ops in run.trace.devices.values()
             if TR.has_collectives(ops)]
    if not chips:
        return None
    return max(TR.exposed_collective_ns(ops) for ops in chips) * 1e-6 / run.steps
