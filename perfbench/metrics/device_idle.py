"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of the device's operation intervals / window)."""


def read(obs):
    import tracereduce

    w = tracereduce.window_s(obs.trace)
    return 100.0 * (1.0 - tracereduce.busy_s(obs.trace) / w) if w > 0 else None
