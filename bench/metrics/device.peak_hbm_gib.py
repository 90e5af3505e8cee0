"""``peak_bytes_in_use`` after the window on the fullest chip, in GiB."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2.0 ** 30
