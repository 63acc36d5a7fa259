"""Device ms per update epoch: the device time of the labelling programs
(the full build and the affected-rows rebuild, the meta-graph APSP, and
the landmark-distance table) inside the traced window, over the epochs
installed in it."""

MODULES = ("jit__build_labelling_arrays", "jit__build_labelling_rows",
           "jit_meta_apsp", "jit__dists_to_landmark_batch")


def read(obs):
    import tracereduce

    s = tracereduce.module_s(obs.trace, MODULES)
    return s * 1e3 / obs.epochs if obs.epochs and s > 0 else None
