"""eager_step_s: the window's wall time over the forward+backward steps
completed in it, host clock."""


def read(r):
    return r.window_s / r.steps
