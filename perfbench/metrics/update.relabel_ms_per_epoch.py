"""Device ms per update epoch in the relabel (scope ``qbs.update.relabel``):
the breadth-first search from the affected landmarks, or from all of
them on a rebuild, inside the traced window, over the update epochs
installed in it."""


def read(obs):
    import epochscope

    return epochscope.scope_ms_per_epoch(obs, "qbs.update.relabel")
