"""device_idle_share: 1 - the union of device op intervals over the traced
window, in %."""


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    return 100.0 * r.trace.idle_share
