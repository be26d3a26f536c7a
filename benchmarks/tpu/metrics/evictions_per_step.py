"""evictions_per_step: the DTR runtime's evictions (rt.evictions) over the
window's steps."""


def read(r):
    if "evictions" not in r.counters:
        return None
    return r.counters["evictions"] / r.steps
