"""Device time of a scope across every program that holds it, over the
update epochs installed in the window.

The update path's programs are compiled at several shapes at once (the
table packing at each packed dtype, the table update at each edge-slot
capacity the run takes), and a trace names an op only by its program's
module and its own name.  ``programtrace.scope_table``
drops an op whose full scope path differs between two compiled programs
of one module; here an op counts toward a scope when every compiled
program of its module that has the op puts it inside that scope, so
programs that differ below the scope still agree on it.
"""
from __future__ import annotations

import programtrace
import tracereduce

_tables: dict = {}


def scope_sets(hlo_texts) -> dict:
    """``{(module, op): names}``: the scope names that every compiled
    program of the module gives the op."""
    table: dict = {}
    for text in hlo_texts:
        m = programtrace._MODULE.search(text)
        if not m:
            continue
        mod = m.group(1)
        for line in text.splitlines():
            hit = programtrace._INSTR.match(line)
            if hit:
                key = (mod, hit.group(1))
                names = frozenset(programtrace.scope_path(hit.group(2)))
                table[key] = table[key] & names if key in table else names
    return table


def op_scopes(obs) -> list:
    """Per device, what each op of the trace is inside of: the recorded
    program's scope paths where the run recorded them (``spans.py``), the
    scope names of the programs this process holds otherwise."""
    pt = getattr(obs, "program", None)
    if pt is not None:
        return [[path for _, path in dev] for dev in pt.scopes]
    tr = obs.trace
    got = _tables.get(id(tr))
    if got is None or got[0] is not tr:
        table = scope_sets(programtrace.live_hlo_texts())
        got = (tr, [[names for _, names in dev]
                    for dev in programtrace.op_scopes(tr, table)])
        _tables[id(tr)] = got
    return got[1]


def scope_ms_per_epoch(obs, scope: str) -> float | None:
    """Device ms inside the window in ops inside ``scope`` (intervals
    united, summed over devices), over the epochs installed in it;
    ``None`` where either is nothing."""
    if not obs.epochs:
        return None
    lo, hi = obs.trace.window
    total = 0.0
    for ops, scoped in zip(obs.trace.ops, op_scopes(obs)):
        mine = [(max(s, lo), min(s + d, hi))
                for (s, d, _), names in zip(ops, scoped) if scope in names]
        total += sum(b - a for a, b in tracereduce._union(
            (a, b) for a, b in mine if b > a))
    return total / 1e6 / obs.epochs if total > 0 else None
