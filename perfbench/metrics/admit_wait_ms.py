"""Mean wait, in ms, from a query's submission to its admission into a
device chunk, over the admissions made inside the window: the admission
and QoS scheduler's counter ``StreamingService.qos_stats[*]["waits"]``."""


def read(obs):
    w = obs.admit_waits_s
    return sum(w) / len(w) * 1e3 if w else None
