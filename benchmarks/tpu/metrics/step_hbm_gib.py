"""step_hbm_gib: the compiled step's footprint from memory_analysis():
arguments + temporaries + outputs - aliased, in GiB."""


def read(r):
    if r.hbm_bytes is None:
        return None
    return r.hbm_bytes / 2**30
