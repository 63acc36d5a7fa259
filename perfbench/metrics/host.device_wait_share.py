"""Share of the traced window, in %, in which the serving thread is
blocked waiting for the device (the union of ``qbs.service.device_wait``
spans): how far the host is from setting the pace.  Read where the run
recorded the program's spans (``obs.program``)."""


def read(obs):
    import programtrace
    import tracereduce

    pt = getattr(obs, "program", None)
    if pt is None:
        return None
    w = tracereduce.window_s(obs.trace)
    s = programtrace.span_union_s(obs.trace, pt, "qbs.service.device_wait")
    return 100.0 * s / w if w > 0 and s > 0 else None
