"""Result bytes copied device to host per pair admitted to a device lane
(the ``bytes`` argument of the ``qbs.service.fetch`` spans that start
inside the traced window, over the landmark-pair, one-sided and general
pairs admitted in it; the program counts the same bytes in
``StreamingService.stats["result_bytes"]``).  Read where the run
recorded the program's spans (``obs.program``)."""


def read(obs):
    import programtrace

    pt = getattr(obs, "program", None)
    n = sum(obs.lane_served[1:])
    if pt is None or not n:
        return None
    b = programtrace.span_arg_sum(obs.trace, pt, "qbs.service.fetch", "bytes")
    return b / n if b > 0 else None
