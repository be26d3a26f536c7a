"""remats_per_step: the DTR runtime's replays (DTRContext.remat_runs) over
the window's steps."""


def read(r):
    if "remat_runs" not in r.counters:
        return None
    return r.counters["remat_runs"] / r.steps
