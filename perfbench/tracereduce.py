"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to plain events, ``(start_ns, duration_ns, name)`` on
one clock:

* ``ops[d]``: the operations device ``d`` ran (the ``XLA Ops`` line of a
  device plane);
* ``modules[d]``: the compiled programs it ran (``XLA Modules``), named
  ``jit_<function>(<id>)``;
* ``spans``: the benchmark's own host spans (``bench.*``), which say what
  the host was doing: submitting, waiting, applying an update.  The span
  ``bench.window`` marks the measured window.

``load_xplane`` reads what ``jax.profiler`` wrote; ``load_json`` reads the
same events from a small JSON file (the tests keep a hand-built one).
Everything below works on the events alone.
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    ops: list = field(default_factory=list)        # per device: [(s, d, name)]
    modules: list = field(default_factory=list)    # per device: [(s, d, name)]
    spans: list = field(default_factory=list)      # [(s, d, name)]

    @property
    def window(self) -> tuple[float, float]:
        """``(start_ns, end_ns)`` of the measured window."""
        for s, d, name in self.spans:
            if name == WINDOW_SPAN:
                return s, s + d
        raise ValueError("trace holds no bench.window span")


def load_xplane(path: Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
            if ops or mods:
                tr.ops.append(ops)
                tr.modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((e.start_ns, e.duration_ns, e.name)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return tr


def load_json(path: Path) -> Trace:
    raw = json.loads(Path(path).read_text())
    as_events = lambda evs: [(float(s), float(d), str(n)) for s, d, n in evs]  # noqa: E731
    return Trace(ops=[as_events(x) for x in raw["ops"]],
                 modules=[as_events(x) for x in raw["modules"]],
                 spans=as_events(raw["spans"]))


def _clip(events, lo: float, hi: float):
    for s, d, name in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield a, b, name


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds inside the window in which some operation ran, averaged
    over the devices that ran any."""
    lo, hi = tr.window
    per_dev = [sum(b - a for a, b in _union((a, b) for a, b, _ in _clip(ops, lo, hi)))
               for ops in tr.ops]
    per_dev = [x for x in per_dev if x > 0]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def window_s(tr: Trace) -> float:
    lo, hi = tr.window
    return (hi - lo) / 1e9


def module_name(event_name: str) -> str:
    """``jit_search_batch(123)`` -> ``jit_search_batch``."""
    return _ID_SUFFIX.sub("", event_name)


def module_s(tr: Trace, names) -> float:
    """Device seconds, inside the window and summed over devices, of the
    compiled programs whose module name is in ``names``."""
    lo, hi = tr.window
    names = set(names)
    return sum(b - a for mods in tr.modules
               for a, b, n in _clip(mods, lo, hi) if module_name(n) in names) / 1e9


def op_events(tr: Trace, pattern: str) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of every operation inside the window whose
    name matches ``pattern`` (a regular expression)."""
    lo, hi = tr.window
    rx = re.compile(pattern)
    return [(a, b) for ops in tr.ops for a, b, n in _clip(ops, lo, hi)
            if rx.search(n)]


def op_label(event_name: str) -> str:
    """An XLA op event carries its HLO instruction text; keep the name:
    ``%while.285 = (s32[] ...) while(...)`` -> ``while.285``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` operations that took the most device time in the window,
    each named ``<module>/<op>`` by the program it ran in:
    ``[[name, seconds], ...]``."""
    lo, hi = tr.window
    tot: dict[str, float] = {}
    for ops, mods in zip(tr.ops, tr.modules):
        spans = sorted((s, s + d, module_name(n)) for s, d, n in mods)
        starts = [s for s, _, _ in spans]
        for a, b, n in _clip(ops, lo, hi):
            i = bisect.bisect_right(starts, a) - 1
            mod = spans[i][2] if i >= 0 and a < spans[i][1] else "?"
            key = f"{mod}/{op_label(n)}"
            tot[key] = tot.get(key, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s / 1e9] for n, s in best]


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest stretches of the window with no operation on the
    first device, each named by the host spans that cover at least half
    of it, joined by ``+`` (several threads can be in spans at once), or
    ``idle`` where none does: ``[[name, seconds], ...]``."""
    lo, hi = tr.window
    ops = tr.ops[0] if tr.ops else []
    busy = _union((a, b) for a, b, _ in _clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(s, s + d, n) for s, d, n in tr.spans if n != WINDOW_SPAN]
    out = []
    for a, b in gaps[:k]:
        cover: dict[str, float] = {}
        for s, e, n in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
        names = sorted(n for n, ov in cover.items() if 2 * ov >= b - a)
        out.append(["+".join(names) or "idle", (b - a) / 1e9])
    return out
