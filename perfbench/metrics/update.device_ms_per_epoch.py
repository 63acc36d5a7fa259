"""Device ms per update epoch: the device time of every op in scope
``qbs.update`` (the device insert test, the affected-row relabel or the
rebuild, the label-table scatters, the meta-graph APSP and the table
packing) inside the traced window, over the update epochs installed in
it."""


def read(obs):
    import epochscope

    return epochscope.scope_ms_per_epoch(obs, "qbs.update")
