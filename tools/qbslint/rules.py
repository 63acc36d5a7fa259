"""The nine QbS repo-invariant rules (see DESIGN.md §9 for rationale).

Every rule is a pure function of one parsed module.  Shared machinery:
``_Aliases`` resolves local names through the file's imports (``import
numpy as np`` makes ``np.asarray`` resolve to ``numpy.asarray``), and
``_dotted`` renders ``a.b.c`` attribute chains.  Rules are deliberately
first-order — no cross-file inference, no type inference — because the
invariants they encode are *syntactic by design*: the repo routes
``shard_map`` through one module, time through one clock, cache inserts
through one method, so the correct program never needs the flagged
constructs outside their home files.
"""
from __future__ import annotations

import ast
from typing import Iterable

from .core import Finding, Module


def _dotted(node: ast.AST) -> str | None:
    """Render a Name/Attribute chain as ``a.b.c`` (None otherwise)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Aliases:
    """Local name -> fully qualified module/attr, from the file's imports."""

    def __init__(self, tree: ast.Module):
        self.map: dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.map[a.asname] = a.name
                    else:
                        root = a.name.split(".")[0]
                        self.map.setdefault(root, root)
            elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                    and node.module:
                for a in node.names:
                    self.map[a.asname or a.name] = f"{node.module}.{a.name}"

    def resolve(self, node: ast.AST) -> str | None:
        d = _dotted(node)
        if d is None:
            return None
        head, _, rest = d.partition(".")
        full = self.map.get(head, head)
        return f"{full}.{rest}" if rest else full


class Rule:
    id = ""
    summary = ""

    def applies(self, path: str) -> bool:
        return True

    def check(self, mod: Module) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, mod: Module, node: ast.AST, message: str) -> Finding:
        return Finding(path=mod.path, line=getattr(node, "lineno", 0),
                       col=getattr(node, "col_offset", 0), rule=self.id,
                       message=message)


# ---------------------------------------------------------------------------
# QBS001 — shard_map only as jax.shard_map
# ---------------------------------------------------------------------------


class ExperimentalShardMap(Rule):
    id = "QBS001"
    summary = ("experimental shard_map imported/used — use jax.shard_map "
               "(vma-checked; the experimental module is the retired 0.4.x "
               "API)")
    _TARGETS = {"jax.experimental.shard_map"}
    _MSG = ("experimental shard_map; use jax.shard_map, whose varying-axes "
            "(vma) checks every program here passes")

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax.experimental.shard_map" or \
                            a.name.startswith("jax.experimental.shard_map."):
                        yield self.finding(mod, node, self._MSG)
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                m = node.module or ""
                names = {a.name for a in node.names}
                if m == "jax.experimental.shard_map" or \
                        (m == "jax.experimental" and "shard_map" in names):
                    yield self.finding(mod, node, self._MSG)
            elif isinstance(node, ast.Attribute):
                if aliases.resolve(node) in self._TARGETS:
                    yield self.finding(mod, node, self._MSG)


# ---------------------------------------------------------------------------
# QBS002 — serving time flows only through the injectable clock
# ---------------------------------------------------------------------------


class WallClockInServing(Rule):
    id = "QBS002"
    summary = ("wall-clock call in serving/ outside clock.py — all serving "
               "time goes through the injectable clock (DESIGN.md §8)")
    _BANNED = {"time.time", "time.monotonic", "time.sleep",
               "threading.Timer"}
    _EXEMPT_FILES = {"clock.py"}

    def applies(self, path: str) -> bool:
        return ("/serving/" in f"/{path}"
                and path.rsplit("/", 1)[-1] not in self._EXEMPT_FILES)

    def _msg(self, what: str) -> str:
        return (f"{what} in serving code; use the injected clock "
                f"(serving.clock) so deadlines stay testable in simulated "
                f"time")

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and node.level == 0:
                m = node.module or ""
                for a in node.names:
                    if f"{m}.{a.name}" in self._BANNED:
                        yield self.finding(mod, node,
                                           self._msg(f"{m}.{a.name}"))
            elif isinstance(node, ast.Attribute):
                full = aliases.resolve(node)
                if full in self._BANNED:
                    yield self.finding(mod, node, self._msg(full))


# ---------------------------------------------------------------------------
# QBS003 — no host syncs inside jitted bodies
# ---------------------------------------------------------------------------


def _is_jit(aliases: _Aliases, node: ast.AST) -> bool:
    return aliases.resolve(node) == "jax.jit"


def _jit_decorated(aliases: _Aliases, fn: ast.AST) -> bool:
    """Is ``fn`` decorated with jax.jit / partial(jax.jit, ...)?"""
    for d in getattr(fn, "decorator_list", []):
        if _is_jit(aliases, d):
            return True
        if isinstance(d, ast.Call):
            if _is_jit(aliases, d.func):
                return True
            if aliases.resolve(d.func) in ("functools.partial", "partial") \
                    and d.args and _is_jit(aliases, d.args[0]):
                return True
    return False


class HostSyncInJit(Rule):
    id = "QBS003"
    summary = ("host-sync call inside a jitted function body (.item(), "
               "int()/float() on arrays, np.asarray, block_until_ready, "
               "device_get) — breaks async dispatch / fails under tracing")

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        contexts: list[ast.AST] = []
        defs_by_name: dict[str, list[ast.AST]] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, []).append(node)
                if _jit_decorated(aliases, node):
                    contexts.append(node)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and _is_jit(aliases, node.func) \
                    and node.args:
                wrapped = node.args[0]
                if isinstance(wrapped, ast.Lambda):
                    contexts.append(wrapped)
                elif isinstance(wrapped, ast.Name):
                    contexts.extend(defs_by_name.get(wrapped.id, []))

        seen: set[tuple[int, int]] = set()
        for ctx in contexts:
            body = ctx.body if isinstance(ctx.body, list) else [ctx.body]
            for stmt in body:
                for f in self._scan(mod, aliases, stmt):
                    key = (f.line, f.col)
                    if key not in seen:
                        seen.add(key)
                        yield f

    def _scan(self, mod: Module, aliases: _Aliases,
              root: ast.AST) -> Iterable[Finding]:
        for node in ast.walk(root):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "item" \
                    and not node.args:
                yield self.finding(mod, node, "'.item()' forces a host "
                                   "sync inside a jitted body")
            elif isinstance(fn, ast.Attribute) \
                    and fn.attr == "block_until_ready":
                yield self.finding(mod, node, "'block_until_ready' inside "
                                   "a jitted body")
            else:
                full = aliases.resolve(fn)
                if full == "jax.device_get":
                    yield self.finding(mod, node, "'jax.device_get' inside "
                                       "a jitted body")
                elif full in ("numpy.asarray", "numpy.array"):
                    yield self.finding(
                        mod, node,
                        f"'{full}' materializes on host inside a jitted "
                        f"body; use jnp")
                elif isinstance(fn, ast.Name) and fn.id in ("int", "float") \
                        and node.args \
                        and not all(isinstance(a, ast.Constant)
                                    for a in node.args):
                    yield self.finding(
                        mod, node,
                        f"'{fn.id}()' on a traced value host-syncs (or "
                        f"raises) inside a jitted body; use jnp casts")


# ---------------------------------------------------------------------------
# QBS004 — jit construction off the setup path
# ---------------------------------------------------------------------------


class JitInHotPath(Rule):
    id = "QBS004"
    summary = ("jax.jit(...) constructed inside a loop or per-call function "
               "body — every construction starts a fresh compile cache "
               "(silent recompile churn on the serving hot path)")
    # "main" is a once-per-process entry point: constructing the jit
    # there (before any loop) is setup, not per-call churn
    _ALLOWED_NAMES = {"__init__", "__post_init__", "__new__",
                      "__init_subclass__", "__set_name__", "main"}
    _ALLOWED_PREFIXES = ("make_", "_make_", "build", "_build",
                         "lower_", "_lower")
    _CACHE_DECOS = {"functools.lru_cache", "functools.cache",
                    "functools.cached_property"}

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        out: list[Finding] = []

        def allowed(fn: ast.AST) -> bool:
            name = fn.name
            if name in self._ALLOWED_NAMES or \
                    name.startswith(self._ALLOWED_PREFIXES):
                return True
            for d in fn.decorator_list:
                base = d.func if isinstance(d, ast.Call) else d
                if aliases.resolve(base) in self._CACHE_DECOS:
                    return True
            return False

        def visit(node: ast.AST, func_frames: tuple, in_loop: bool) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for d in node.decorator_list:
                    visit(d, func_frames, in_loop)
                frames = func_frames + (allowed(node),)
                for child in node.body:
                    visit(child, frames, False)
                return
            if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                for child in ast.iter_child_nodes(node):
                    visit(child, func_frames, True)
                return
            if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                 ast.GeneratorExp)):
                for child in ast.iter_child_nodes(node):
                    visit(child, func_frames, True)
                return
            if isinstance(node, ast.Call) and _is_jit(aliases, node.func):
                if in_loop:
                    out.append(self.finding(
                        mod, node, "jax.jit(...) constructed inside a loop; "
                        "hoist it to a make_*/build* factory or __init__"))
                elif func_frames and not func_frames[-1]:
                    out.append(self.finding(
                        mod, node, "jax.jit(...) constructed in a per-call "
                        "body; hoist it to a make_*/build* factory, "
                        "__init__, or an lru_cache'd helper"))
            for child in ast.iter_child_nodes(node):
                visit(child, func_frames, in_loop)

        visit(mod.tree, (), False)
        return out


# ---------------------------------------------------------------------------
# QBS005 — lock discipline over declared guarded fields
# ---------------------------------------------------------------------------


def _guard_root(expr: ast.AST) -> str | None:
    """For ``self.X``/``self.X[...]``/``self.X[...].Y...`` return ``X``."""
    prev = None
    while isinstance(expr, (ast.Subscript, ast.Attribute)):
        prev = expr
        expr = expr.value
    if isinstance(expr, ast.Name) and expr.id == "self" \
            and isinstance(prev, ast.Attribute):
        return prev.attr
    return None


def _literal_strings(node: ast.AST) -> set[str] | None:
    """String constants of a tuple/list/set literal, unwrapping
    ``frozenset({...})`` / ``set([...])`` / ``tuple((...))`` calls."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("frozenset", "set", "tuple") \
            and len(node.args) == 1:
        node = node.args[0]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        elems = node.elts
        if all(isinstance(e, ast.Constant) and isinstance(e.value, str)
               for e in elems):
            return {e.value for e in elems}
    return None


class LockDiscipline(Rule):
    id = "QBS005"
    summary = ("mutation of a _QBS_GUARDED_FIELDS field outside a "
               "'with self._lock' block (timer threads race the driver)")
    _MUTATORS = {"append", "appendleft", "extend", "extendleft", "insert",
                 "pop", "popleft", "popitem", "remove", "discard", "clear",
                 "update", "add", "setdefault", "sort", "reverse", "rotate"}
    _HEAP_FNS = {"heapq.heappush", "heapq.heappop", "heapq.heapreplace",
                 "heapq.heappushpop", "heapq.heapify"}

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            fields = self._guarded_fields(cls)
            if not fields:
                continue
            for item in cls.body:
                if not isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if item.name == "__init__" or mod.is_locked_def(item):
                    continue
                yield from self._scan_body(mod, aliases, item.body, fields,
                                           locked=False)

    def _guarded_fields(self, cls: ast.ClassDef) -> set[str] | None:
        for item in cls.body:
            targets = []
            if isinstance(item, ast.Assign):
                targets = item.targets
            elif isinstance(item, ast.AnnAssign) and item.value is not None:
                targets = [item.target]
            for t in targets:
                if isinstance(t, ast.Name) and t.id == "_QBS_GUARDED_FIELDS":
                    return _literal_strings(item.value)
        return None

    def _is_lock_ctx(self, withitem: ast.withitem) -> bool:
        return _dotted(withitem.context_expr) == "self._lock"

    def _scan_body(self, mod: Module, aliases: _Aliases, stmts: list,
                   fields: set[str], locked: bool) -> Iterable[Finding]:
        for stmt in stmts:
            yield from self._scan_stmt(mod, aliases, stmt, fields, locked)

    def _scan_stmt(self, mod: Module, aliases: _Aliases, node: ast.AST,
                   fields: set[str], locked: bool) -> Iterable[Finding]:
        if isinstance(node, (ast.With, ast.AsyncWith)):
            now_locked = locked or any(self._is_lock_ctx(i)
                                       for i in node.items)
            if not locked:
                for i in node.items:
                    yield from self._scan_calls(mod, aliases,
                                                i.context_expr, fields)
            yield from self._scan_body(mod, aliases, node.body, fields,
                                       now_locked)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a closure may run on another thread after the lock is
            # released — conservatively treat its body as unlocked
            yield from self._scan_body(mod, aliases, node.body, fields,
                                       locked=False)
            return
        if not locked:
            # statement-level target mutations
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in self._flat_targets(targets):
                    root = _guard_root(t)
                    if root in fields:
                        yield self.finding(mod, t, self._msg(root, "write"))
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    root = _guard_root(t)
                    if root in fields:
                        yield self.finding(mod, t,
                                           self._msg(root, "delete"))
            # mutating calls anywhere in this statement's expressions
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.expr):
                    yield from self._scan_calls(mod, aliases, child, fields)
        # nested statements (If/For/Try bodies, handlers, ...)
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, ast.expr):
                yield from self._scan_stmt(mod, aliases, child, fields,
                                           locked)

    def _msg(self, field: str, how: str) -> str:
        return (f"{how} of guarded field 'self.{field}' outside "
                f"'with self._lock' (mark the method '# qbslint: locked' "
                f"if its contract is caller-holds-lock)")

    def _scan_calls(self, mod: Module, aliases: _Aliases, expr: ast.AST,
                    fields: set[str]) -> Iterable[Finding]:
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr in self._MUTATORS:
                root = _guard_root(fn.value)
                if root in fields:
                    yield self.finding(
                        mod, node, self._msg(root, f"'.{fn.attr}()' call"))
            elif aliases.resolve(fn) in self._HEAP_FNS and node.args:
                root = _guard_root(node.args[0])
                if root in fields:
                    yield self.finding(
                        mod, node, self._msg(root, "heapq mutation"))

    @staticmethod
    def _flat_targets(targets: list) -> Iterable[ast.AST]:
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                yield from LockDiscipline._flat_targets(t.elts)
            elif isinstance(t, ast.Starred):
                yield t.value
            else:
                yield t


# ---------------------------------------------------------------------------
# QBS006 — all cache inserts via ServingService.cache_put
# ---------------------------------------------------------------------------


class CacheInsertBypass(Rule):
    id = "QBS006"
    summary = ("ResultCache write bypassing ServingService.cache_put — "
               "the admission policy (reuse prediction, shadow set) only "
               "sees inserts routed through cache_put")

    _INTERNALS = {"_store", "_protected"}

    def check(self, mod: Module) -> Iterable[Finding]:
        yield from self._visit(mod, mod.tree, class_stack=(), func_stack=())

    @staticmethod
    def _chain_has_cache(node: ast.AST) -> bool:
        d = _dotted(node)
        if d is None:
            return False
        return any(seg == "cache" or seg.endswith("_cache")
                   for seg in d.split("."))

    def _visit(self, mod: Module, node: ast.AST, class_stack: tuple,
               func_stack: tuple) -> Iterable[Finding]:
        if isinstance(node, ast.ClassDef):
            class_stack = class_stack + (node.name,)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            func_stack = func_stack + (node.name,)

        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "put" \
                and self._chain_has_cache(node.func.value) \
                and "cache_put" not in func_stack:
            yield self.finding(
                mod, node, "direct cache .put(); route the insert through "
                "ServingService.cache_put so the admission policy applies")
        elif isinstance(node, ast.Attribute) \
                and node.attr in self._INTERNALS \
                and "ResultCache" not in class_stack \
                and self._chain_has_cache(node.value):
            yield self.finding(
                mod, node, f"touching ResultCache internal '.{node.attr}' "
                "outside the ResultCache class; use get()/cache_put()")

        for child in ast.iter_child_nodes(node):
            yield from self._visit(mod, child, class_stack, func_stack)


# ---------------------------------------------------------------------------
# QBS007 — packed tables never widen to >= 32 bits in host code
# ---------------------------------------------------------------------------


def _jit_spans(aliases: _Aliases, tree: ast.Module) -> list[tuple[int, int]]:
    """Line spans of every jit context in the module (same collection rule
    as QBS003: jit-decorated defs, ``jax.jit(fn)`` on a named def, and
    ``jax.jit(lambda ...)``)."""
    contexts: list[ast.AST] = []
    defs_by_name: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
            if _jit_decorated(aliases, node):
                contexts.append(node)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit(aliases, node.func) \
                and node.args:
            wrapped = node.args[0]
            if isinstance(wrapped, ast.Lambda):
                contexts.append(wrapped)
            elif isinstance(wrapped, ast.Name):
                contexts.extend(defs_by_name.get(wrapped.id, []))
    return [(c.lineno, getattr(c, "end_lineno", None) or c.lineno)
            for c in contexts]


class PackedWidenOnHost(Rule):
    id = "QBS007"
    summary = ("host-side widening of a packed label/cache table to >= 32 "
               "bits — packed uint8/uint16 arrays only widen in registers "
               "inside jit bodies (DESIGN.md §10); a resident int32 copy "
               "forfeits the 4x label-bandwidth win")
    # the packed-table field names (core.packing.PackedLabels and the
    # QbSIndex attributes that alias them)
    _PACKED_NAMES = {"label_dist", "meta_w", "meta_dist",
                     "lm_dist", "_lm_dist"}
    _WIDE = {"numpy.int32", "numpy.int64",
             "jax.numpy.int32", "jax.numpy.int64"}
    _WIDE_STRS = {"int32", "int64", "i4", "i8"}

    @classmethod
    def _is_packed_expr(cls, node: ast.AST) -> bool:
        while isinstance(node, ast.Subscript):
            node = node.value
        d = _dotted(node)
        if d is None:
            return False
        segs = d.split(".")
        return segs[-1] in cls._PACKED_NAMES \
            or any("packed" in s for s in segs)

    def _is_wide_dtype(self, aliases: _Aliases, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value in self._WIDE_STRS
        return aliases.resolve(node) in self._WIDE

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        spans = _jit_spans(aliases, mod.tree)
        in_serving = "/serving/" in f"/{mod.path}"

        def in_jit(node: ast.AST) -> bool:
            line = getattr(node, "lineno", 0)
            return any(lo <= line <= hi for lo, hi in spans)

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "astype" and node.args \
                    and self._is_wide_dtype(aliases, node.args[0]) \
                    and self._is_packed_expr(node.func.value) \
                    and not in_jit(node):
                yield self.finding(
                    mod, node, "packed table widened to >= int32 in host "
                    "code; gather the packed rows and widen inside the jit "
                    "body (core.packing.widen_dist) so the int32 copy "
                    "lives in registers, not HBM")
            elif in_serving and isinstance(node, ast.Attribute) \
                    and aliases.resolve(node) == "numpy.int64" \
                    and not in_jit(node):
                yield self.finding(
                    mod, node, "np.int64 on the serving path; the serving "
                    "host tier is int32-audited (edge ids, cache values) — "
                    "if 64 bits are genuinely required, say why and add "
                    "'# qbslint: disable=QBS007'")


# ---------------------------------------------------------------------------
# QBS008 — sharded tables never gathered whole to host
# ---------------------------------------------------------------------------


class NoReplicatedGather(Rule):
    id = "QBS008"
    summary = ("host gather (jax.device_get / np.asarray) of a sharded "
               "table in serving/ or the sharded core — full-table "
               "materialization silently rebuilds the replicated copy the "
               "vertex-sharded index exists to avoid (DESIGN.md §11); "
               "declared host boundaries mark the def "
               "'# qbslint: host-boundary'")
    _GATHERS = {"jax.device_get", "numpy.asarray", "numpy.array",
                "jax.numpy.asarray", "jax.numpy.array"}
    _FILES = {"distributed.py", "sharded.py"}

    def applies(self, path: str) -> bool:
        return ("/serving/" in f"/{path}"
                or path.rsplit("/", 1)[-1] in self._FILES)

    @staticmethod
    def _is_sharded_expr(node: ast.AST) -> bool:
        """Does the (Subscript-stripped) receiver chain name a sharded
        table?  Convention (core.distributed / core.sharded): mesh-resident
        arrays carry an ``_sh`` suffix or a ``sharded`` segment."""
        while isinstance(node, ast.Subscript):
            node = node.value
        d = _dotted(node)
        if d is None:
            return False
        segs = d.split(".")
        return segs[-1].endswith("_sh") or any("sharded" in s for s in segs)

    def check(self, mod: Module) -> Iterable[Finding]:
        aliases = _Aliases(mod.tree)
        spans = [(n.lineno, getattr(n, "end_lineno", None) or n.lineno)
                 for n in ast.walk(mod.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                 and mod.is_host_boundary_def(n)]

        def in_boundary(node: ast.AST) -> bool:
            line = getattr(node, "lineno", 0)
            return any(lo <= line <= hi for lo, hi in spans)

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and node.args \
                    and aliases.resolve(node.func) in self._GATHERS \
                    and self._is_sharded_expr(node.args[0]) \
                    and not in_boundary(node):
                yield self.finding(
                    mod, node, "host gather of a sharded table ('*_sh' / "
                    "'sharded' receiver) outside a declared host boundary; "
                    "serve from the shards, or — if this def IS the "
                    "checkpoint/debug boundary — mark it "
                    "'# qbslint: host-boundary'")


# ---------------------------------------------------------------------------
# QBS009 — graph/label tables mutate only through epoch-advance entry points
# ---------------------------------------------------------------------------


class TableMutationOutsideEpoch(Rule):
    id = "QBS009"
    summary = ("write to a Graph/label-table/index attribute outside a "
               "construction or epoch-advance entry point — dynamic "
               "updates route through apply_update/install_index so every "
               "table swap advances the epoch and in-flight chunks stay "
               "pinnable to theirs (DESIGN.md §13)")
    # the versioned state: rebinding any of these (or writing into one
    # in place) changes what an index — or a service holding one —
    # answers for, which only an epoch advance may do
    _TABLES = {"graph", "scheme", "packed", "labels", "index",
               "label_dist", "meta_w", "meta_dist", "lm_dist",
               "_lm_dist", "_lm_dist_host", "src", "dst", "indptr"}
    # construction plus the §13 epoch-advance entry points
    _ALLOWED_NAMES = {"__init__", "__post_init__", "__new__",
                      "apply_update", "submit_update", "install_index",
                      "apply_edge_updates"}
    _ALLOWED_PREFIXES = ("build", "_build", "make_", "_make_", "from_")

    def _allowed(self, name: str) -> bool:
        return name in self._ALLOWED_NAMES \
            or name.startswith(self._ALLOWED_PREFIXES)

    @staticmethod
    def _strip(node: ast.AST) -> ast.AST:
        while isinstance(node, ast.Subscript):
            node = node.value
        return node

    def check(self, mod: Module) -> Iterable[Finding]:
        yield from self._visit(mod, mod.tree, allowed=False)

    def _visit(self, mod: Module, node: ast.AST,
               allowed: bool) -> Iterable[Finding]:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            allowed = self._allowed(node.name)
        elif not allowed and isinstance(
                node, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                       ast.Delete)):
            targets = (node.targets if isinstance(node,
                                                  (ast.Assign, ast.Delete))
                       else [node.target])
            for t in LockDiscipline._flat_targets(targets):
                t = self._strip(t)
                if isinstance(t, ast.Attribute) and t.attr in self._TABLES:
                    how = ("delete of" if isinstance(node, ast.Delete)
                           else "write to")
                    yield self.finding(
                        mod, t, f"{how} table attribute '.{t.attr}' "
                        f"outside an epoch-advance entry point; build a "
                        f"new index via apply_update and swap it in with "
                        f"install_index so the epoch advances with the "
                        f"tables")
        for child in ast.iter_child_nodes(node):
            yield from self._visit(mod, child, allowed)


ALL_RULES = (ExperimentalShardMap(), WallClockInServing(), HostSyncInJit(),
             JitInHotPath(), LockDiscipline(), CacheInsertBypass(),
             PackedWidenOnHost(), NoReplicatedGather(),
             TableMutationOutsideEpoch())
RULES_BY_ID = {r.id: r for r in ALL_RULES}
