"""train_tokens_per_s: every token trained in the window over the
window's wall time, host clock."""


def read(r):
    return r.work / r.window_s
