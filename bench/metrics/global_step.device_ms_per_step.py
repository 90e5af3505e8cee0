"""Device milliseconds per outer step in the program's ``dsm_global_step``
scope (worker mean, sign-momentum update; on four chips the ZeRO
reduce-scatter and update), averaged over the chips."""

from harness import trace as TR


def read(run):
    per_chip = [TR.scope_ns(ops, run.op_names, "dsm_global_step")
                for ops in run.trace.devices.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) * 1e-6 / run.steps
