"""Host ms per device chunk in dispatch (span ``qbs.service.dispatch``:
the chunk's index arrays to the device and the lane program's launch),
inside the traced window, over the chunks dispatched in it.  Read where
the run recorded the program's spans (``obs.program``)."""


def read(obs):
    import programtrace

    return programtrace.span_ms_per_chunk(obs, "qbs.service.dispatch")
