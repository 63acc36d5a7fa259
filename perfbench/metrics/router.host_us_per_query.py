"""Host us per query in the router (span ``qbs.router.route``: the
router's lock, the owner lookup and the grouping by replica, not the
replica's own submit), inside the traced window, over the routing calls
made in it (one a query where queries are submitted one at a time).
Read where the run recorded the program's spans (``obs.program``)."""


def read(obs):
    import programtrace

    pt = getattr(obs, "program", None)
    if pt is None:
        return None
    n = programtrace.span_count(obs.trace, pt, "qbs.router.route")
    s = programtrace.span_s(obs.trace, pt, "qbs.router.route")
    return s * 1e6 / n if n and s > 0 else None
