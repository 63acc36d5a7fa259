"""Host ms per device chunk in the planner (span ``qbs.planner.plan``:
lane classification of an admitted batch), inside the traced window,
over the chunks dispatched in it.  Read where the run recorded the
program's spans (``obs.program``)."""


def read(obs):
    import programtrace

    return programtrace.span_ms_per_chunk(obs, "qbs.planner.plan")
