"""Device ms per general-lane pair in the sketch phase (scope ``qbs.sketch``:
the label-row gathers and the min-plus sketch, kernel included) of the
general lane's program, inside the traced window, over the general-lane
pairs admitted in it."""


def read(obs):
    import programtrace

    return programtrace.scope_ms_per_pair(obs, "jit_search_batch", "qbs.sketch", 3)
