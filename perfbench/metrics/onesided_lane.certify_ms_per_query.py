"""Device ms per one-sided pair in the lane's certify pass (scope
``qbs.onesided.certify``: the landmark-distance row gather and the test
of every edge slot against the BFS depths), inside the traced window,
over the one-sided pairs admitted in it."""


def read(obs):
    import programtrace

    return programtrace.scope_ms_per_pair(obs, "jit__landmark_onesided_lanes", "qbs.onesided.certify", 2)
