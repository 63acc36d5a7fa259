"""Async SPG serving service: lane execution over a ``QueryPlan``
(DESIGN.md §4).

The service owns *how* a planned batch runs; the planner owns *what* runs
(``serving.planner``).  Execution policy:

* **Double-buffered async dispatch.**  Every lane chunk is a jitted device
  program returning un-synced device arrays; the service keeps up to
  ``async_depth`` chunks in flight and only blocks on the oldest when the
  window is full.  Host post-processing of chunk k (``device_get``,
  per-row ``flatnonzero``, ``SPGResult`` assembly) therefore overlaps the
  device computing chunk k+1.  ``async_depth=1`` degenerates to the
  seed's strictly synchronous dispatch-then-sync loop and exists as the
  benchmark baseline (``benchmarks.serving_throughput``).
* **Result cache.**  An optional cache keyed on the canonical pair
  ``(min(u, v), max(u, v))`` — the same key the planner dedups on — plus
  the serving epoch (DESIGN.md §13: a cached SPG from an earlier graph
  version must never answer a later query), mapping to
  ``(dist, edge_ids)``.  SPGs are orientation-invariant on an
  undirected graph, so one entry serves both directions.  Cache lookups
  happen at plan time (hit rows leave their lanes before any chunking);
  inserts happen as chunks drain.  ``cache_policy="lru"`` is plain LRU;
  ``"hub"`` reserves *protected slots* for entries whose endpoints are
  landmarks or high-degree hubs (``Graph.hub_mask``) — the hub-skew
  eviction policy of DESIGN.md §5: hot hub pairs ride out floods of
  one-shot cold traffic that would evict them from a pure LRU.
  ``cache_admission="reuse"`` additionally refuses *insertion* of
  predicted one-shot cold pairs (non-hub keys are only admitted on their
  second sighting) — DESIGN.md §8.
* **Multi-device.**  With ``mesh=`` (or ``devices=``), general-lane chunks
  run batch-sharded across local devices through
  ``core.distributed.make_serve_step`` (replicated graph/labels, queries
  split over the mesh via ``jax.shard_map``), then re-enter the
  shared symmetrization program.  Landmark lanes stay single-device: they
  are label lookups plus one bounded BFS, never the serving bottleneck.

``QbSIndex.query_batch`` / ``query_batch_arrays`` and
``serving.serve_spg_batch`` are thin delegates over a default service
(``async_depth=2``, no cache, single device), so all scale policy lives
here.
"""
from __future__ import annotations

import warnings
from collections import OrderedDict, deque
from functools import partial
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import INF
from ..tracing import span
from .planner import (
    LANE_GENERAL,
    LANE_LANDMARK_PAIR,
    LANE_ONE_SIDED,
    LANE_TRIVIAL,
    N_LANES,
    QueryPlan,
    chunk_padded,
    d_top_of,
    onesided_roots,
    plan_queries,
)

_NO_EDGES = np.zeros((0,), np.int32)   # edge counts fit int32 (E << 2^31)
_NO_EDGES.flags.writeable = False   # shared by every trivial-lane result


def _launch(step: Callable, *host_args):
    """One chunk's dispatch: its host index arrays to the device, then
    the lane program, launched without a sync."""
    with span("qbs.service.dispatch"):
        return step(*(jnp.asarray(a) for a in host_args))


def fetch_chunk(out, stats: dict) -> tuple[np.ndarray, np.ndarray]:
    """Wait for a dispatched chunk ``(dist, edge_mask)``, then copy it to
    the host; the copy's bytes add to ``stats["result_bytes"]``.  A
    general-lane chunk's recover job count (``core.qbs.GeneralChunk``)
    adds to ``stats["recover_jobs"]``."""
    nbytes = sum(x.nbytes for x in out)
    jobs = getattr(out, "recover_jobs", None)
    with span("qbs.service.device_wait"):
        jax.block_until_ready(out)
    with span("qbs.service.fetch", bytes=nbytes):
        d, m = jax.device_get(out)
    stats["result_bytes"] += nbytes
    if jobs is not None:
        stats["recover_jobs"] += int(jobs)
    return d, m


def _pack_result(value: tuple[int, np.ndarray]) -> tuple:
    """Pack a ``(dist, edge_ids)`` result for cache residency (DESIGN.md
    §10): int32 edge ids, delta-encoded as uint16 gaps when the sorted
    (``flatnonzero``-built) id list allows it — the anchor id stays int32.
    Returns ``(nbytes, dist, enc)``; ``nbytes`` feeds the byte-based
    capacity accounting."""
    dist, eids = value
    eids = np.asarray(eids)
    if eids.dtype != np.int32:
        eids = eids.astype(np.int32)
        eids.flags.writeable = False
    if eids.size > 1:
        deltas = np.diff(eids)
        if deltas.min() >= 0 and deltas.max() < (1 << 16):
            d16 = deltas.astype(np.uint16)
            d16.flags.writeable = False
            # 2 bytes per gap + 4-byte anchor + uint16 dist
            return d16.nbytes + 6, int(dist), ("delta", int(eids[0]), d16)
    return eids.nbytes + 2, int(dist), ("raw", eids)


def _unpack_result(entry: tuple) -> tuple[int, np.ndarray]:
    """Decode a packed cache entry back to ``(dist, edge_ids int32)``.
    Decoded arrays are frozen like every shared result array."""
    _, dist, enc = entry
    if enc[0] == "raw":
        return dist, enc[1]
    _, first, d16 = enc
    eids = np.empty((d16.size + 1,), np.int32)
    eids[0] = first
    eids[1:] = d16
    np.cumsum(eids, out=eids)
    eids.flags.writeable = False
    return dist, eids


class ResultCache:
    """``(dist, edge_ids)`` cache keyed on the canonical query pair plus
    the serving epoch (``(u, v, epoch)`` — DESIGN.md §13: an entry
    computed under one epoch must never answer a query admitted under a
    later one, so the epoch rides in the key and stale entries simply
    stop being reachable).  The cache itself is key-shape-agnostic; the
    ``protect`` predicate only ever reads ``key[0]``/``key[1]``.

    Without ``protect`` this is a plain LRU.  With ``protect`` (a predicate
    on the canonical key), ``protected_frac`` of the capacity becomes
    *protected slots*: accepted keys live in their own LRU tier that cold
    traffic cannot evict — eviction always drains the unprotected tier
    first, and protected entries only leave when their own tier overflows
    (the LRU protected entry then *demotes* into the unprotected tier
    rather than dropping).  This is the hub-skew eviction policy: landmark-
    and hub-endpoint pairs dominate repeat traffic, so they keep their
    slots under floods of one-shot pairs.

    Values live *packed* (``_pack_result``: int32/delta-uint16 edge ids)
    and decode on ``get``; ``self.bytes`` tracks the packed payload bytes
    and ``capacity_bytes`` optionally bounds them alongside the entry
    count, so capacity can be provisioned in memory rather than entries.

    ``capacity=0`` is a valid no-op cache: every ``get`` misses and ``put``
    stores nothing (callers can keep the cache object unconditionally).
    """

    def __init__(self, capacity: int, *,
                 protect: Callable[[tuple[int, int]], bool] | None = None,
                 protected_frac: float = 0.5,
                 capacity_bytes: int | None = None):
        if capacity < 0:
            raise ValueError("cache capacity must be non-negative")
        if capacity_bytes is not None and capacity_bytes < 0:
            raise ValueError("cache capacity_bytes must be non-negative")
        self.capacity = int(capacity)
        self.capacity_bytes = (
            None if capacity_bytes is None else int(capacity_bytes))
        self.protect = protect
        self.protected_cap = (
            max(1, int(capacity * protected_frac))
            if protect is not None and capacity else 0)
        # both tiers map key -> (nbytes, dist, enc) packed entries
        self._store: OrderedDict[tuple[int, int], tuple] = (
            OrderedDict())   # unprotected LRU tier
        self._protected: OrderedDict[tuple[int, int], tuple] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0   # entries dropped by capacity pressure
        self.bytes = 0       # packed payload bytes currently resident

    def __len__(self) -> int:
        return len(self._store) + len(self._protected)

    def __contains__(self, key: tuple[int, int]) -> bool:
        return key in self._store or key in self._protected

    def get(self, key: tuple[int, int]):
        for tier in (self._protected, self._store):
            got = tier.get(key)
            if got is not None:
                tier.move_to_end(key)
                self.hits += 1
                return _unpack_result(got)
        self.misses += 1
        return None

    def _evict_one(self) -> None:
        _, entry = (self._store or self._protected).popitem(last=False)
        self.bytes -= entry[0]
        self.evictions += 1

    def bytes_for(self, keys) -> int:
        """Packed resident bytes attributable to ``keys`` (canonical
        pairs; absent keys contribute 0) — the per-replica memory
        attribution the partitioned-cache acceptance checks read."""
        total = 0
        for key in keys:
            entry = self._store.get(key)
            if entry is None:
                entry = self._protected.get(key)
            if entry is not None:
                total += entry[0]
        return total

    def put(self, key: tuple[int, int], value: tuple[int, np.ndarray]) -> None:
        self._insert_packed(key, _pack_result(value))

    def _insert_packed(self, key: tuple, entry: tuple) -> None:
        """Insert one already-packed ``(nbytes, dist, enc)`` entry — the
        shared tail of ``put`` and ``import_packed`` (tier choice,
        demotion, capacity pressure)."""
        if self.capacity == 0:
            return
        # a key lives in exactly one tier; re-put refreshes tier + recency
        old = self._store.pop(key, None)
        if old is None:
            old = self._protected.pop(key, None)
        if old is not None:
            self.bytes -= old[0]
        self.bytes += entry[0]
        if self.protected_cap and self.protect(key):
            self._protected[key] = entry
            while len(self._protected) > self.protected_cap:
                k, v = self._protected.popitem(last=False)
                self._store[k] = v   # demote, don't drop
        else:
            self._store[key] = entry
        while len(self) > self.capacity:
            self._evict_one()
        if self.capacity_bytes is not None:
            while self.bytes > self.capacity_bytes and len(self):
                self._evict_one()

    def export_packed(self, pred=None, *, remove: bool = False) -> list:
        """Export resident entries *packed* — ``[(key, (nbytes, dist,
        enc)), ...]`` in LRU-to-MRU order, so importing in list order
        reproduces the recency order here.  ``pred`` filters on the key;
        ``remove=True`` also evicts the exported entries (a *move*, the
        replica warm-handoff path: the pair's bytes must live on exactly
        one replica, matching the routing invariant)."""
        out = []
        for tier in (self._store, self._protected):
            keys = [k for k in tier if pred is None or pred(k)]
            for k in keys:
                out.append((k, tier[k]))
                if remove:
                    entry = tier.pop(k)
                    self.bytes -= entry[0]
        return out

    def import_packed(self, entries) -> None:
        """Absorb entries exported by a peer's ``export_packed``.  The
        receiving cache re-applies its *own* tier policy per key (replica
        tiers are homogeneous, so a hub-protected entry lands protected
        again) and its own capacity pressure."""
        for key, entry in entries:
            self._insert_packed(key, entry)


def round_chunk_to_shards(chunk: int, n_shards: int) -> int:
    """Round ``chunk`` up to a multiple of ``n_shards`` (the sharded
    general lane splits every chunk evenly across the mesh devices)."""
    if chunk <= 0:
        raise ValueError("chunk must be positive")
    if n_shards <= 1 or chunk % n_shards == 0:
        return chunk
    return ((chunk + n_shards - 1) // n_shards) * n_shards


class ServingService:
    """Planner-routed, lane-overlapped executor over a built ``QbSIndex``."""

    def __init__(self, index, *, async_depth: int = 2, cache_size: int = 0,
                 cache_policy: str = "lru", protected_frac: float = 0.5,
                 hub_top_frac: float = 0.01, cache_admission: str = "all",
                 cache_size_bytes: int | None = None,
                 chunk: int | None = None, mesh=None, devices=None):
        self.index = index
        self.chunk = int(index.chunk if chunk is None else chunk)
        self.async_depth = max(1, int(async_depth))
        self.cache = None
        if cache_size or cache_size_bytes:
            if cache_policy == "lru":
                protect = None
            elif cache_policy == "hub":
                protect = self._hub_protect(hub_top_frac)
            else:
                raise ValueError(f"unknown cache_policy={cache_policy!r}")
            # byte-only provisioning: entry count is unbounded, the packed
            # payload bytes are the capacity (ResultCache accounting)
            cap = cache_size if cache_size else (1 << 62)
            self.cache = ResultCache(cap, protect=protect,
                                     protected_frac=protected_frac,
                                     capacity_bytes=cache_size_bytes)
        # Cache *admission* (insertion) is a separate axis from eviction
        # (cache_policy): "all" inserts every computed result (the seed
        # behavior); "reuse" refuses predicted one-shot cold pairs — a key
        # is inserted only when an endpoint is a landmark/top-degree hub
        # (the traffic skew that predicts repetition, ``Graph.hub_mask``)
        # or when it is seen a second time (a bounded shadow set records
        # first sightings), so a flood of never-repeated cold pairs cannot
        # churn the cache at all, whatever the eviction policy.
        if cache_admission not in ("all", "reuse"):
            raise ValueError(f"unknown cache_admission={cache_admission!r}")
        self.cache_admission = cache_admission
        self._seen_once: OrderedDict | None = None
        if self.cache is not None and cache_admission == "reuse":
            # share the eviction policy's predicate when it exists so the
            # two hub policies can never diverge on hub_top_frac (and the
            # degree sort in Graph.hub_mask runs once)
            self._admit_hot = (self.cache.protect
                               if self.cache.protect is not None
                               else self._hub_protect(hub_top_frac))
            self._seen_once = OrderedDict()
            self._seen_cap = max(64, 4 * min(self.cache.capacity, 1 << 16))
        self.lane_served = [0] * N_LANES   # unique pairs answered per lane
        # service-level counters (the scheduler's stats live on the
        # streaming layer); chunk_roundings counts admission-time widths
        # rounded up to the shard multiple (warned once, counted always);
        # recover_jobs counts the general lane's recover jobs (not on the
        # batch-sharded path, whose step returns no count)
        self.stats = {"chunk_roundings": 0, "installs": 0,
                      "result_bytes": 0, "recover_jobs": 0}
        self._warned_rounding = False

        if (mesh is not None or devices is not None) and getattr(
                index, "is_sharded", False):
            # a ShardedIndex is already mesh-resident: its lane steps run
            # vertex-sharded over their own mesh (core.sharded), so batch-
            # sharding the general lane on top would need the replicated
            # ctx/scheme tables the sharded index exists to not hold
            raise ValueError(
                "mesh=/devices= batch sharding cannot wrap a sharded index; "
                "ShardedIndex serves from its own mesh already")
        if mesh is None and devices is not None:
            from jax.sharding import Mesh
            if isinstance(devices, int):
                avail = jax.devices()
                if len(avail) < devices:
                    raise ValueError(
                        f"devices={devices} requested but only "
                        f"{len(avail)} visible")
                devs = avail[:devices]
            else:
                devs = list(devices)
            mesh = Mesh(np.array(devs), ("q",))
        self._sharded_general = None
        self._n_shards = 1
        self._mesh = mesh
        if mesh is not None:
            self._n_shards = int(np.prod(list(mesh.shape.values())))
            rounded = round_chunk_to_shards(self.chunk, self._n_shards)
            if rounded != self.chunk:
                self._warned_rounding = True
                warnings.warn(
                    f"chunk={self.chunk} does not divide over "
                    f"{self._n_shards} shards; rounding up to {rounded}",
                    stacklevel=2)
                self.chunk = rounded
            self._sharded_general = self._make_sharded_general()

    def _make_sharded_general(self):
        from ..core.distributed import make_serve_step
        index = self.index
        return make_serve_step(
            index.ctx, index.scheme, self._mesh,
            n_vertices=index.graph.n_vertices,
            max_levels=index.max_levels, max_chain=index.max_chain,
            use_pallas=index.use_pallas, packed=index.packed)

    def install_index(self, index) -> None:
        """Swap in the next epoch's index (an ``apply_update`` product —
        DESIGN.md §13).  Chunks dispatched before the swap already hold
        device handles to the old epoch's tables, so their results stay
        bit-consistent with their admission epoch; the result cache
        survives the swap — its keys carry the epoch, so entries written
        under earlier epochs simply stop being reachable and age out
        under normal eviction pressure.  The hub-protect predicate stays
        pinned at construction (landmarks are pinned across epochs; the
        hub set is an eviction heuristic, not a correctness surface).

        Callers must serialize this against the query entry points —
        ``StreamingService.install_index`` does, under its scheduler
        lock; bare services are single-caller by contract."""
        if getattr(index, "is_sharded", False):
            raise ValueError("cannot install a sharded index")
        if index.epoch <= self.index.epoch:
            raise ValueError(
                f"install_index: epoch {index.epoch} is not ahead of "
                f"serving epoch {self.index.epoch}")
        self.index = index
        self.stats["installs"] += 1
        if self._mesh is not None:
            self._sharded_general = self._make_sharded_general()

    def _hub_protect(self, hub_top_frac: float):
        """Protect predicate for the hub-skew cache policy: a canonical
        pair is protected when either endpoint is a landmark or a
        top-degree hub (``Graph.hub_mask``)."""
        prot = self.index._is_landmark_np | self.index.graph.hub_mask(
            top_frac=hub_top_frac)
        return lambda key: bool(prot[key[0]] or prot[key[1]])

    # -- lane dispatch -------------------------------------------------------

    def _general_step(self, cu, cv):
        if self._sharded_general is None:
            return self.index.serve_step(cu, cv)
        mask, dist = self._sharded_general(cu, cv)
        from ..core.qbs import _symmetrize
        return _symmetrize(dist, mask, self.index._rev_edge_j)

    def _chunks(self, plan: QueryPlan, chunk: int | None = None):
        """Yield ``(unique_rows (chunk,), live, dispatch)`` per lane chunk.
        ``dispatch()`` copies the chunk's index arrays to the device,
        enqueues the lane program and returns un-synced device arrays
        ``(dist (chunk,), edge_mask (chunk, E))``.

        ``chunk`` overrides the service's width for this plan (the
        streaming admission layer picks it adaptively); every jitted lane
        step caches one compile per width, so callers should draw widths
        from a small fixed set.  Sharded services round the override up
        to the shard multiple — warned once per service instance and
        counted in ``stats['chunk_roundings']`` every time, so streaming
        traffic with a misaligned adaptive ladder shows up in metrics
        instead of spamming one warning per admission."""
        if chunk is None:
            chunk = self.chunk
        else:
            rounded = round_chunk_to_shards(int(chunk), self._n_shards)
            if rounded != chunk:
                self.stats["chunk_roundings"] += 1
                if not self._warned_rounding:
                    self._warned_rounding = True
                    warnings.warn(
                        f"admitted chunk={chunk} does not divide over "
                        f"{self._n_shards} shards; rounding up to "
                        f"{rounded} (warned once; see "
                        f"stats['chunk_roundings'])", stacklevel=2)
            chunk = rounded
        idx = self.index
        lid = idx._lid_np

        for sel, live in chunk_padded(plan.lanes[LANE_GENERAL], chunk):
            yield sel, live, partial(_launch, self._general_step,
                                     plan.cu[sel], plan.cv[sel])

        for sel, live in chunk_padded(plan.lanes[LANE_LANDMARK_PAIR],
                                      chunk):
            yield sel, live, partial(_launch, idx.landmark_pair_step,
                                     lid[plan.cu[sel]], lid[plan.cv[sel]])

        one = plan.lanes[LANE_ONE_SIDED]
        if one.size:
            roots, r_idx = onesided_roots(plan.cu[one], plan.cv[one],
                                          idx._is_landmark_np, lid)
            for pos, live in chunk_padded(np.arange(one.size), chunk):
                yield one[pos], live, partial(_launch,
                                              idx.landmark_onesided_step,
                                              roots[pos], r_idx[pos])

    def _execute(self, plan: QueryPlan) -> Iterator[tuple]:
        """Drain all device lanes: yields host tuples ``(unique_rows,
        dist (L,), edge_mask (L, E))`` with up to ``async_depth`` chunks in
        flight (the double buffer: chunk k+1 is enqueued before chunk k is
        *synced*, so host post-processing overlaps device compute).

        The overlap pays where host and device are separate silicon (the
        accelerator serving regime this targets); on a small CPU host the
        "device" programs share cores with this thread, so sync and async
        converge to parity there (pinned by
        ``benchmarks/serving_throughput.py``)."""
        inflight: deque = deque()

        def drain(limit: int):
            while len(inflight) > limit:
                sel, live, out = inflight.popleft()
                d, m = fetch_chunk(out, self.stats)
                yield sel[:live], d[:live], m[:live]

        for sel, live, dispatch in self._chunks(plan):
            inflight.append((sel, live, dispatch()))
            yield from drain(self.async_depth - 1)
        yield from drain(0)

    # -- cache ---------------------------------------------------------------

    def _cache_partition(self, plan: QueryPlan):
        """Pull cache hits out of the device lanes.  Returns the reduced
        plan plus ``[(unique_row, dist, edge_ids), ...]`` hits."""
        if self.cache is None:
            return plan, []
        hits = []
        epoch = self.index.epoch
        lanes = list(plan.lanes)
        for k in (LANE_LANDMARK_PAIR, LANE_ONE_SIDED, LANE_GENERAL):
            miss = []
            for row in lanes[k]:
                got = self.cache.get(
                    (int(plan.cu[row]), int(plan.cv[row]), epoch))
                if got is None:
                    miss.append(row)
                else:
                    hits.append((int(row), got[0], got[1]))
            lanes[k] = np.asarray(miss, dtype=np.intp)
        return plan._replace(lanes=tuple(lanes)), hits

    def cache_put(self, key: tuple[int, int, int],
                  value: tuple[int, np.ndarray]) -> None:
        """Insert a computed result through the cache *admission* policy
        (the one insertion path — the streaming scheduler routes through
        it too, so admission policy cannot drift between entry points).
        ``key`` is the epoched cache key ``(u, v, epoch)``."""
        if self.cache is None:
            return
        if self._seen_once is not None and key not in self.cache \
                and not self._admit_hot(key):
            if key not in self._seen_once:       # predicted one-shot: skip
                self._seen_once[key] = None
                while len(self._seen_once) > self._seen_cap:
                    self._seen_once.popitem(last=False)
                return
            del self._seen_once[key]             # second sighting: admit
        self.cache.put(key, value)

    def _cache_put(self, plan: QueryPlan, row: int, dist: int,
                   eids: np.ndarray) -> None:
        self.cache_put(
            (int(plan.cu[row]), int(plan.cv[row]), self.index.epoch),
            (int(dist), eids))

    # -- answers -------------------------------------------------------------

    def _answer_unique(self, plan: QueryPlan):
        """Answer every unique pair: ``(dist (U,) int32, edge_ids list)``."""
        u_dist = np.full((plan.n_unique,), INF, np.int32)
        u_eids: list = [None] * plan.n_unique
        for row in plan.lanes[LANE_TRIVIAL]:
            u_dist[row] = 0
            u_eids[row] = _NO_EDGES
        for k in range(N_LANES):
            self.lane_served[k] += int(plan.lanes[k].size)
        plan, hits = self._cache_partition(plan)
        for row, d, eids in hits:
            u_dist[row] = d
            u_eids[row] = eids
        for rows, d, m in self._execute(plan):
            for k, row in enumerate(rows):
                eids = np.flatnonzero(m[k]).astype(np.int32)
                # Frozen because the array is shared: duplicate queries fan
                # it out to several results and the cache hands it back on
                # later hits — an in-place mutation by a caller must not
                # corrupt either.
                eids.flags.writeable = False
                u_dist[row] = d[k]
                u_eids[row] = eids
                if self.cache is not None:
                    self._cache_put(plan, row, int(d[k]), eids)
        return u_dist, u_eids

    def query_batch(self, us, vs) -> list:
        """Arbitrary batch -> per-query ``SPGResult`` list (original
        orientation preserved; dedup/canonicalization are internal).

        ``edge_ids`` arrays are read-only and may be shared between
        duplicate queries and with the result cache."""
        from ..core.qbs import SPGResult
        us = np.asarray(us, np.int32).reshape(-1)
        vs = np.asarray(vs, np.int32).reshape(-1)
        plan = plan_queries(us, vs, self.index._is_landmark_np)
        u_dist, u_eids = self._answer_unique(plan)
        out = []
        for i in range(plan.n):
            row = plan.inv[i]
            d = int(u_dist[row])
            out.append(SPGResult(u=int(us[i]), v=int(vs[i]), dist=d,
                                 edge_ids=u_eids[row],
                                 d_top=d_top_of(int(plan.lane[row]), d, INF)))
        return out

    def query_arrays(self, us, vs) -> tuple[np.ndarray, np.ndarray]:
        """Arbitrary batch -> raw ``(dist (N,) int32, edge_mask (N, E)
        bool)`` arrays with no per-query result objects.  Same
        routing/cache/execution as ``query_batch`` (one shared
        ``_answer_unique``); only the result assembly differs."""
        us = np.asarray(us, np.int32).reshape(-1)
        vs = np.asarray(vs, np.int32).reshape(-1)
        plan = plan_queries(us, vs, self.index._is_landmark_np)
        u_dist, u_eids = self._answer_unique(plan)
        # one dense mask, filled per query from the (sparse) unique-row
        # edge ids — peak host memory stays a single (N, E) array however
        # many duplicates the batch carries
        mask = np.zeros((plan.n, self.index.graph.n_edges), bool)
        for i, row in enumerate(plan.inv):
            mask[i, u_eids[row]] = True
        return u_dist[plan.inv], mask
