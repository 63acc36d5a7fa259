"""Device ms per general-lane pair in recover (scope ``qbs.recover``: the
edges of shortest paths through landmarks, taken from the labels one
landmark at a time) of the general lane's program, inside the traced
window, over the general-lane pairs admitted in it."""


def read(obs):
    import programtrace

    return programtrace.scope_ms_per_pair(obs, "jit_search_batch", "qbs.recover", 3)
