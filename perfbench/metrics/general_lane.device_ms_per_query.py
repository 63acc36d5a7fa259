"""Device ms per general-lane pair: the device time of the general lane's
programs (sketch plus guided search, and the edge-mask symmetrization)
inside the traced window, over the general-lane pairs admitted in it."""

MODULES = ("jit_search_batch", "jit__symmetrize")


def read(obs):
    import tracereduce

    n = obs.lane_served[3]
    s = tracereduce.module_s(obs.trace, MODULES)
    return s * 1e3 / n if n and s > 0 else None
