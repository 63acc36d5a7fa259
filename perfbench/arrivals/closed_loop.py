"""Arrival kind ``closed_loop``: ``clients`` clients, each sending its
next query as soon as its last one is answered.  Parameter: ``clients``.

An arrival process tells the driver's loop how many queries to send now
(``ready``, asked on every pass of the loop; an idle loop passes every
half millisecond) and when the pre-roll has reached the steady state the
window measures (``steady``).
"""

PREROLL_RETURNS = 3


class Arrivals:
    def __init__(self, spec: dict, rng):
        self.clients = int(spec["clients"])
        if self.clients < 1:
            raise ValueError("closed_loop: clients must be positive")

    def ready(self, now: float, outstanding: int) -> int:
        return self.clients - outstanding

    def steady(self, n_submitted: int, n_returns: int) -> bool:
        # every client has sent, and answers have come back and been sent
        # again PREROLL_RETURNS times: from here each answer frees a
        # client at once, and the first chunks after the warm-up stay out
        # of the window
        return n_submitted >= self.clients and n_returns >= PREROLL_RETURNS
