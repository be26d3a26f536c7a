"""setup_s: seconds from the start of the process to the start of the
measured window: JAX start-up, weights, compilation or the compile cache,
warm-up and, for the train job, the first steps that the reference follows.
"""


def read(r):
    return r.setup_s
