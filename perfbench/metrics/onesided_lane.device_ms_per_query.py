"""Device ms per one-sided pair: the device time of the one-sided
landmark lane's program (a batched, distance-bounded full-graph BFS plus
the certify pass) inside the traced window, over the one-sided pairs
admitted in it."""

MODULES = ("jit__landmark_onesided_lanes",)


def read(obs):
    import tracereduce

    n = obs.lane_served[2]
    s = tracereduce.module_s(obs.trace, MODULES)
    return s * 1e3 / n if n and s > 0 else None
