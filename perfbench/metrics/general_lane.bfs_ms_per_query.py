"""Device ms per general-lane pair in the guided bidirectional BFS on G-
(scope ``qbs.bfs``) of the general lane's program, inside the traced
window, over the general-lane pairs admitted in it."""


def read(obs):
    import programtrace

    return programtrace.scope_ms_per_pair(obs, "jit_search_batch", "qbs.bfs", 3)
