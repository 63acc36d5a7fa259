"""Device ms per one-sided pair in the lane's distance-bounded full-graph
BFS (scope ``qbs.onesided.bfs``), inside the traced window, over the
one-sided pairs admitted in it."""


def read(obs):
    import programtrace

    return programtrace.scope_ms_per_pair(obs, "jit__landmark_onesided_lanes", "qbs.onesided.bfs", 2)
