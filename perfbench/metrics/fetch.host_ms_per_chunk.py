"""Host ms per device chunk in the result copy (span
``qbs.service.fetch``: the chunk's distances and edge mask, device to
host, after the device has finished), inside the traced window, over the
chunks dispatched in it.  Read where the run recorded the program's
spans (``obs.program``)."""


def read(obs):
    import programtrace

    return programtrace.span_ms_per_chunk(obs, "qbs.service.fetch")
