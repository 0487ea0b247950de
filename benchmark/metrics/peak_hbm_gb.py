"""Peak bytes in use on the fullest chip, read from the device after the
window (before anything else runs), in GB."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
