"""Host ms per device chunk in resolution (span ``qbs.stream.resolve``:
each row's edge ids from the mask, its futures resolved, the cache
filled), inside the traced window, over the chunks dispatched in it.
Read where the run recorded the program's spans (``obs.program``)."""


def read(obs):
    import programtrace

    return programtrace.span_ms_per_chunk(obs, "qbs.stream.resolve")
