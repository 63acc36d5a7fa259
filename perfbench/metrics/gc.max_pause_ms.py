"""The longest pause of Python's garbage collector inside the traced
window, in ms (span ``qbs.gc``, one per collection, recorded while the
run holds ``repro.tracing.gc_spans``).  Read where the run recorded the
program's spans (``obs.program``)."""


def read(obs):
    import programtrace

    pt = getattr(obs, "program", None)
    s = None if pt is None else programtrace.span_max_s(obs.trace, pt, "qbs.gc")
    return s * 1e3 if s is not None else None
