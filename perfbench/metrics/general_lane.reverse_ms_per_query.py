"""Device ms per general-lane pair in the reverse search (scope
``qbs.reverse``: the sweeps back from the meeting level that mark the
landmark-free shortest-path edges) of the general lane's program, inside
the traced window, over the general-lane pairs admitted in it."""


def read(obs):
    import programtrace

    return programtrace.scope_ms_per_pair(obs, "jit_search_batch", "qbs.reverse", 3)
