"""device_idle: share of the traced window in which no operation
ran on the device (1 - union of op intervals / window)."""


def read(r):
    return None if r.trace is None else 100.0 * r.trace.idle_share
