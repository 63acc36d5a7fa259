"""The program's own names in a profiler trace: the host spans and the
device scopes of the serving path (``repro.tracing``).

``tracereduce`` keeps the device planes and the benchmark's ``bench.*``
spans; this module adds what the program names itself, beside it and on
the same clock:

* ``spans``: the ``qbs.*`` host spans, ``(start_ns, duration_ns, name,
  line, args)``, where ``line`` names the thread (so self time can be
  taken) and ``args`` holds the span's arguments;
* ``scopes``: for each device op of ``Trace.ops``, in a parallel list,
  the program it ran in and its scope path (``("jit(search_batch)",
  "qbs.recover", "while", ...)``) from the op's HLO metadata.  The
  trace's op events carry only the op's name, so the path comes from
  the compiled text of the programs this process still holds (``jax``
  keeps every compiled executable alive in its caches), looked up by
  module and op name.

Helpers take times inside the measured window (``bench.window``).  A
metric reader finds the program's names as ``obs.program`` where the run
recorded them (``perfbench/spans.py``), and the scopes alone from the
live programs otherwise (``of_obs``).
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

import tracereduce

PREFIX = "qbs."
_MODULE = re.compile(r"HloModule ([\w.\-]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"")
_WRAP = re.compile(r"^[\w\-]+\((.*)\)$")


@dataclass
class ProgramTrace:
    spans: list = field(default_factory=list)    # [(s, d, name, line, args)]
    scopes: list = field(default_factory=list)   # per device, per op: (module, path)


def scope_table(hlo_texts) -> dict:
    """``{(module, op): scope path}`` from compiled HLO text.  An op name
    that two compiled programs of one module name use for different
    paths maps to no path."""
    table: dict = {}
    for text in hlo_texts:
        m = _MODULE.search(text)
        if not m:
            continue
        mod = m.group(1)
        for line in text.splitlines():
            hit = _INSTR.match(line)
            if hit:
                key, path = (mod, hit.group(1)), scope_path(hit.group(2))
                table[key] = path if table.get(key, path) == path else ()
    return table


def scope_path(op_name: str) -> tuple:
    """``jit(f)/vmap(qbs.bfs)/while`` -> ``("jit(f)", "qbs.bfs", "while")``:
    a transformation's wrapper around a scope's name is dropped."""
    out = []
    for part in op_name.split("/"):
        while (w := _WRAP.match(part)) and not part.startswith("jit("):
            part = w.group(1)
        out.append(part)
    return tuple(out)


def live_hlo_texts() -> list[str]:
    """The optimized HLO text of every program this process has compiled
    and still holds."""
    import jax

    texts = []
    for exe in jax.devices()[0].client.live_executables():
        try:
            texts.extend(m.to_string() for m in exe.hlo_modules())
        except Exception:  # an executable that keeps no HLO has no scopes
            continue
    return texts


def op_scopes(tr, table: dict) -> list:
    """Per device, ``(module, scope path)`` of each op of ``tr.ops``: the
    program the op ran in, and ``()`` where ``table`` has no path."""
    out = []
    for ops, mods in zip(tr.ops, tr.modules):
        spans = sorted((s, s + d, tracereduce.module_name(n)) for s, d, n in mods)
        starts = [s for s, _, _ in spans]
        got = []
        for s, _, n in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = spans[i][2] if i >= 0 and s < spans[i][1] else None
            got.append((mod, table.get((mod, tracereduce.op_label(n)), ())))
        out.append(got)
    return out


_live: dict = {}


def of_live(tr) -> ProgramTrace:
    """The scopes of ``tr``'s ops from the programs this process holds
    (computed once per trace); no host spans."""
    got = _live.get(id(tr))
    if got is None or got[0] is not tr:
        got = (tr, ProgramTrace(scopes=op_scopes(tr, scope_table(live_hlo_texts()))))
        _live[id(tr)] = got
    return got[1]


def of_obs(obs) -> ProgramTrace:
    """What a metric reader can see of the program's names."""
    pt = getattr(obs, "program", None)
    return pt if pt is not None else of_live(obs.trace)


def load_xplane(path: Path, tr) -> ProgramTrace:
    """The ``qbs.*`` host spans of the trace at ``path`` and the scopes
    of ``tr``'s ops (``tr`` read from the same file)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                where = f"{plane.name}/{k}:{line.name}"
                spans.extend((e.start_ns, e.duration_ns, e.name, where,
                              dict(e.stats))
                             for e in line.events if e.name.startswith(PREFIX))
    return ProgramTrace(spans=spans, scopes=of_live(tr).scopes)


def load_json(path: Path):
    """``(Trace, ProgramTrace)`` of a hand-built JSON trace: ``tracereduce``'s
    keys plus ``program_spans`` (``[s, d, name, line, args]``) and ``hlo``
    (compiled text that gives the ops' scopes)."""
    raw = json.loads(Path(path).read_text())
    tr = tracereduce.load_json(path)
    spans = [(float(s), float(d), str(n), str(w), dict(a))
             for s, d, n, w, a in raw["program_spans"]]
    return tr, ProgramTrace(spans=spans, scopes=op_scopes(tr, scope_table(raw["hlo"])))


def _clipped(pt: ProgramTrace, name: str, lo: float, hi: float):
    for s, d, n, w, _ in pt.spans:
        a, b = max(s, lo), min(s + d, hi)
        if n == name and b > a:
            yield a, b, w


def span_s(tr, pt: ProgramTrace, name: str) -> float:
    """Seconds inside the window spent in spans named ``name``, summed
    over threads."""
    lo, hi = tr.window
    return sum(b - a for a, b, _ in _clipped(pt, name, lo, hi)) / 1e9


def span_max_s(tr, pt: ProgramTrace, name: str) -> float | None:
    """The longest span named ``name`` inside the window, in seconds."""
    lo, hi = tr.window
    return max(((b - a) / 1e9 for a, b, _ in _clipped(pt, name, lo, hi)), default=None)


def span_union_s(tr, pt: ProgramTrace, name: str) -> float:
    """Seconds inside the window in which some span named ``name`` is open."""
    lo, hi = tr.window
    return sum(b - a for a, b in tracereduce._union(
        (a, b) for a, b, _ in _clipped(pt, name, lo, hi))) / 1e9


def span_count(tr, pt: ProgramTrace, name: str) -> int:
    """Spans named ``name`` that start inside the window."""
    lo, hi = tr.window
    return sum(1 for s, _, n, _, _ in pt.spans if n == name and lo <= s < hi)


def span_arg_sum(tr, pt: ProgramTrace, name: str, arg: str) -> float:
    """The sum of argument ``arg`` over the spans named ``name`` that start
    inside the window."""
    lo, hi = tr.window
    return sum(float(a.get(arg, 0)) for s, _, n, _, a in pt.spans
               if n == name and lo <= s < hi)


def self_s(tr, pt: ProgramTrace, name: str) -> float:
    """``span_s`` less the time in which another program span runs
    nested inside it on the same thread."""
    lo, hi = tr.window
    lines: dict = {}
    for s, d, n, w, _ in pt.spans:
        lines.setdefault(w, []).append((s, s + d, n))
    total = 0.0
    for line in lines.values():
        line.sort(key=lambda x: (x[0], -x[1]))    # a parent before its children
        starts = [s for s, _, _ in line]
        for k, (s0, e0, n0) in enumerate(line):
            a, b = max(s0, lo), min(e0, hi)
            if n0 != name or b <= a:
                continue
            inner = [(max(s, a), min(e, b)) for s, e, _ in
                     line[k + 1:bisect.bisect_right(starts, e0)] if e <= e0]
            total += (b - a) - sum(y - x for x, y in tracereduce._union(
                (x, y) for x, y in inner if y > x))
    return total / 1e9


def scope_s(tr, pt: ProgramTrace, module: str, scope: str) -> float:
    """Device seconds inside the window, summed over devices, in which an
    op of ``module`` whose scope path holds ``scope`` ran.  Intervals are
    united, so a ``while`` and the ops of its body count once."""
    lo, hi = tr.window
    total = 0.0
    for ops, scoped in zip(tr.ops, pt.scopes):
        mine = [(max(s, lo), min(s + d, hi))
                for (s, d, _), (mod, path) in zip(ops, scoped)
                if mod == module and scope in path]
        total += sum(b - a for a, b in tracereduce._union(
            (a, b) for a, b in mine if b > a))
    return total / 1e9


def idle_gaps_program(tr, pt: ProgramTrace, k: int = 10) -> list[list]:
    """``tracereduce.idle_gaps``'s ``k`` gaps, each named by the innermost
    program span, per thread, that covers at least half of it, joined by
    ``+`` across threads (each name once), or ``idle``:
    ``[[name, seconds], ...]``."""
    lo, hi = tr.window
    ops = tr.ops[0] if tr.ops else []
    busy = tracereduce._union((a, b) for a, b, _ in tracereduce._clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        inner: dict[str, tuple] = {}     # thread -> (start, -length, name)
        for s, d, n, w, _ in pt.spans:
            if 2 * (min(b, s + d) - max(a, s)) >= b - a:
                inner[w] = max(inner.get(w, (s, -d, n)), (s, -d, n))
        names = sorted({n for _, _, n in inner.values()})
        out.append(["+".join(names) or "idle", (b - a) / 1e9])
    return out


def scope_ms_per_pair(obs, module: str, scope: str, lane: int) -> float | None:
    """Device ms in ``scope`` of ``module`` over the pairs of lane ``lane``
    admitted in the window (``None`` where either is nothing)."""
    n = obs.lane_served[lane]
    s = scope_s(obs.trace, of_obs(obs), module, scope)
    return s * 1e3 / n if n and s > 0 else None


def span_ms_per_chunk(obs, name: str) -> float | None:
    """Host ms in spans named ``name`` over the chunks dispatched in the
    window (``None`` where the run recorded no program spans)."""
    pt = getattr(obs, "program", None)
    if pt is None:
        return None
    chunks = span_count(obs.trace, pt, "qbs.service.dispatch")
    s = span_s(obs.trace, pt, name)
    return s * 1e3 / chunks if chunks and s > 0 else None
