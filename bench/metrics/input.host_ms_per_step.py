"""Host milliseconds per outer step spent making the batch and putting it
on the device: the harness's ``input`` spans in the traced window."""


def read(run):
    spans = [b - a for a, b, name in run.trace.host if name == "input"]
    if not spans:
        return None
    return sum(spans) * 1e-6 / run.steps
