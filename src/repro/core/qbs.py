"""Query-by-Sketch facade: offline labelling + online planner-routed
serving.

Usage::

    index = QbSIndex.build(graph, n_landmarks=20)
    res = index.query(u, v)              # one SPG
    res = index.query_batch(us, vs)      # batched serving

Online serving is a two-layer planner/executor architecture (DESIGN.md
§4).  ``serving.planner`` classifies a batch into lanes over canonical
deduplicated pairs — trivial (u == v), landmark-landmark (label-only
certify), one-sided landmark (label distance + one bounded BFS), and
general (sketch + guided search) — and ``serving.service`` executes the
lanes as fixed-shape jitted chunks with double-buffered async dispatch, an
optional LRU result cache, and an optional batch-sharded multi-device
mode.  ``query_batch`` / ``query_batch_arrays`` here are thin delegates
over a default service; this module owns the per-lane *device steps*:

* ``serve_step`` — the general lane: label gather -> sketch (Eq. 3
  min-plus on the Pallas kernel when ``use_pallas=True``, the default) ->
  guided search of the chunk (``search.search_chunk``) -> device-side
  edge-mask symmetrization through the precomputed reverse-edge map.
* ``landmark_pair_step`` / ``landmark_onesided_step`` — the vectorized
  landmark lanes.  Queries whose endpoint *is* a landmark have no label
  entries and no presence in G-, but their distance is exact from label
  rows + meta-graph APSP alone: any shortest u->r path splits at its first
  interior landmark r' into a labelled u->r' prefix and a meta-graph
  r'->r suffix, so d(u, r) = min_i L(u, i) + d_M(i, r).  Landmark-landmark
  SPGs certify every edge directly from the two label-derived distance
  fields; one-sided queries add a single *distance-bounded* full-graph BFS
  from the non-landmark endpoint, batched over the whole lane through
  ``frontier.bfs_depths_batch``.  Landmarks are the highest-degree hubs,
  so this traffic dominates under real skew — it runs as jitted
  fixed-shape lanes exactly like the general path, never a per-query host
  loop.

All frontier relays (guided search and the landmark lane's bounded BFS) go
through the pluggable ``core.frontier`` engine; ``backend=`` selects the
relay implementation at construction like ``use_pallas`` selects the
sketch kernel.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property, lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .frontier import bfs_depths_batch, make_relay
from .graph import (
    INF,
    EdgeSlots,
    Graph,
    apply_edge_updates,
    select_landmarks,
)
from .labelling import (
    LabellingScheme,
    affected_landmarks,
    build_labelling,
    update_tables,
)
from .packing import (
    choose_pack_dtype,
    pack_labelling,
    pack_tables,
    widen_dist,
)
from .search import (
    Query,
    make_search_context,
    search_chunk,
)
from .sketch import compute_sketch_batch
from ..tracing import scope


@dataclass(frozen=True)
class SPGResult:
    """One shortest-path-graph answer (host types)."""

    u: int
    v: int
    dist: int                 # INF if disconnected
    edge_ids: np.ndarray      # directed edge-slot ids, symmetrized
    d_top: int

    def edge_pairs(self, graph: Graph) -> set[tuple[int, int]]:
        s = np.asarray(graph.src)[self.edge_ids]
        d = np.asarray(graph.dst)[self.edge_ids]
        return {(int(min(a, b)), int(max(a, b))) for a, b in zip(s, d)}

    def vertices(self, graph: Graph) -> set[int]:
        s = np.asarray(graph.src)[self.edge_ids]
        d = np.asarray(graph.dst)[self.edge_ids]
        out = set(map(int, s)) | set(map(int, d))
        if self.dist == 0:
            out |= {self.u}
        return out


class _LaneChunk(NamedTuple):
    dist: jax.Array        # (B,) int32
    edge_mask: jax.Array   # (B, E) bool, symmetrized


class GeneralChunk(_LaneChunk):
    """A general-lane chunk's device results: unpacks, flattens and
    copies to the host as ``(dist, edge_mask)`` like every lane step's,
    and carries ``recover_jobs``, the () int32 count of jobs recover ran
    (``search.recover_chunk``), which ``serving.service.fetch_chunk``
    adds to ``stats["recover_jobs"]``.  An attribute, not a field, so the
    pair's contract is unchanged; a rebuilt copy (``jax.device_get``)
    holds ``None``."""

    def __new__(cls, dist, edge_mask, recover_jobs=None):
        out = super().__new__(cls, dist, edge_mask)
        out.recover_jobs = recover_jobs
        return out


@jax.jit
def _symmetrize(dist, mask, rev_edge):
    """Device-side edge-mask symmetrization.  Jitted *separately* from the
    search program: fused into it, the gather makes XLA pick a slower
    layout for the loop-carried (B, E) edge mask (~25% per-chunk
    regression on CPU); as its own program the gather costs single-digit
    ms.  Module-level so all indexes share one compile cache entry —
    nothing here is instance-specific."""
    return dist, mask | mask[:, rev_edge]


def _reverse_edge_map(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    key = src.astype(np.int64) * n + dst.astype(np.int64)
    rkey = dst.astype(np.int64) * n + src.astype(np.int64)
    order = np.argsort(key, kind="stable")
    pos = np.searchsorted(key[order], rkey)
    return order[pos].astype(np.int32)


# -- landmark-lane device steps (module-level: one jit cache entry) ----------


@jax.jit
def _dists_to_landmark(label_dist, meta_dist, lid, is_landmark, r_idx):
    """(V,) exact d_G(x, landmark r_idx) from label rows + meta APSP.
    Dual-mode inputs: packed tables widen in-register (core.packing)."""
    label_dist = widen_dist(label_dist)
    meta_dist = widen_dist(meta_dist)
    col = meta_dist[:, r_idx]                               # (R,)
    base = jnp.min(label_dist + col[None, :], axis=1)       # non-landmark rows
    at_lm = meta_dist[jnp.clip(lid, 0, None), r_idx]
    return jnp.minimum(jnp.where(is_landmark, at_lm, base), INF).astype(jnp.int32)


@jax.jit
def _certify_spg_edges(src, dst, rev_edge, du_all, dv_all, d):
    """Edge (x, y) lies on a shortest u-v path iff du(x) + 1 + dv(y) == d;
    symmetrized to both orientations like every SPG edge mask.  The
    symmetrized mask is invariant under swapping du/dv, so callers never
    need to track which side holds the landmark."""
    mask = (du_all[src] + 1 + dv_all[dst]) == d
    return mask | mask[rev_edge]


@jax.jit
def _dists_to_landmark_batch(label_dist, meta_dist, lid, is_landmark, r_idx):
    """Vectorized lane form: (B,) landmark indices -> (B, V) distances."""
    fn = partial(_dists_to_landmark, label_dist, meta_dist, lid, is_landmark)
    return jax.vmap(fn)(r_idx)


_certify_spg_edges_batch = jax.vmap(
    _certify_spg_edges, in_axes=(None, None, None, 0, 0, 0))


@jax.jit
def _landmark_pair_lanes(lm_dist, meta_dist, src, dst, rev_edge, ru, rv):
    """Landmark-landmark lane: (B,) landmark index pairs -> (dist (B,),
    edge_mask (B, E)).  Distance is a ``meta_dist`` lookup; every SPG edge
    certifies from two rows of the precomputed (R, V) landmark-distance
    table ``lm_dist`` — no search, no per-chunk recomputation.  Both tables
    arrive packed; only the gathered rows widen (in registers)."""
    d = jnp.minimum(widen_dist(meta_dist[ru, rv]), INF).astype(jnp.int32)
    mask = _certify_spg_edges_batch(src, dst, rev_edge,
                                    widen_dist(lm_dist[ru]),
                                    widen_dist(lm_dist[rv]), d)
    return d, mask & (d < INF)[:, None]


@partial(jax.jit, static_argnames=("max_levels",))
def _landmark_onesided_lanes(engine, lm_dist, src, dst, rev_edge,
                             roots, r_idx, *, max_levels: int):
    """One-sided landmark lane: (B,) non-landmark roots + (B,) landmark
    indices -> (dist (B,), edge_mask (B, E)).  One batched full-graph BFS,
    each row bounded at its own d - 1 (those shortest paths may pass
    *through* landmarks, so the G- engine is wrong here — ``engine`` is
    the unmasked full-graph relay)."""
    with scope("qbs.onesided.certify"):
        to_lm = widen_dist(lm_dist[r_idx])              # (B, V)
        d = to_lm[jnp.arange(roots.shape[0]), roots]
    with scope("qbs.onesided.bfs"):
        bounds = jnp.where(d < INF, d - 1, 0)  # disconnected rows never expand
        depth = bfs_depths_batch(engine, roots, max_levels, bounds=bounds)
    with scope("qbs.onesided.certify"):
        mask = _certify_spg_edges_batch(src, dst, rev_edge, to_lm, depth, d)
        return d, mask & (d < INF)[:, None]


@lru_cache(maxsize=None)
def _make_search_batch(n_vertices: int, max_levels: int, max_chain: int,
                       use_pallas: bool):
    """General-lane search program, cached on its static configuration so
    epoch-advanced indexes (``apply_update`` — same V/E capacity, new
    tables) reuse the compiled program instead of re-jitting per index."""
    def search_batch(ctx, label_dist, meta_w, meta_dist, us, vs):
        # gather the *packed* rows from HBM; compute_sketch_batch
        # widens them (and the packed meta tables) in registers
        with scope("qbs.sketch"):
            lu = label_dist[us]
            lv = label_dist[vs]
            sk = compute_sketch_batch(lu, lv, meta_w, meta_dist,
                                      use_pallas=use_pallas)
        queries = Query(
            u=us, v=vs, d_top=sk.d_top,
            du_land=sk.du_land, dv_land=sk.dv_land,
            meta_edge=sk.meta_edge,
            d_star_u=sk.d_star_u, d_star_v=sk.d_star_v,
        )
        res = search_chunk(ctx, queries, n_vertices, max_levels, max_chain)
        return res.dist, res.edge_mask, res.recover_jobs

    # Chained with the module-level _symmetrize program in serve_step:
    # two jit dispatches, everything on device, no host sync (see
    # _symmetrize for why the gather is not fused in here).
    return jax.jit(search_batch)


class QbSIndex:
    is_sharded = False   # replicated tables; core.sharded.ShardedIndex flips it

    def __init__(self, graph: Graph, scheme: LabellingScheme, *,
                 max_levels: int = 512, max_chain: int = 512, chunk: int = 32,
                 use_pallas: bool = True, backend: str = "segment",
                 engine_opts: dict | None = None,
                 epoch: int = 0, lm_dist=None, packed=None,
                 slots: EdgeSlots | None = None, unreached=None,
                 full_engine=None, landmark_host=None):
        self.graph = graph
        self.scheme = scheme
        self.max_levels = max_levels
        self.max_chain = max_chain
        self.chunk = chunk
        # Read-only records of the construction choices: the jitted pipeline
        # captures them below, so mutating these attributes has no effect —
        # rebuild the index to switch sketch paths or relay backends.
        self.use_pallas = use_pallas
        self.backend = backend
        # Epoch of the graph this index answers for (DESIGN.md §13); an
        # ``apply_update`` batch returns a new index at ``epoch + 1``,
        # stamping how the update resolved (affected set / rebuild) here.
        self.epoch = epoch
        self.last_update_info: dict = {}

        engine_opts = engine_opts or {}
        self._engine_opts = dict(engine_opts)
        # Host mirror of the graph's live edge slots (``graph.EdgeSlots``):
        # ``apply_update`` hands each epoch's index the mirror its graph was
        # built from, so no update copies the CSR back from the device.  A
        # built index makes it at its first update.
        self._slots = slots
        relay_kw = dict(engine_opts)
        if slots is not None:
            relay_kw["host"] = slots.csr
        # (R, V) exact vertex-to-landmark distances, a pure function of the
        # labelling — built once here so the landmark lane steps gather
        # rows instead of re-reducing the label matrix every chunk.
        # ``apply_update`` passes the incrementally-maintained table in
        # (bit-identical: both are exact BFS distances with the same INF).
        # The table may still be in the making on the device (an update's);
        # ``_lm_dist_host`` copies it out where the host needs it.
        if lm_dist is None:
            lm_dist = _dists_to_landmark_batch(
                scheme.label_dist, scheme.meta_dist, scheme.lid,
                scheme.is_landmark, jnp.arange(scheme.n_landmarks))
        self._lm_table = lm_dist
        # (V,) bool: vertices some landmark does not reach (``apply_update``
        # passes its own, computed lazily otherwise).
        self._unreached_mask = unreached
        # The packed label tables (uint8/uint16 + INF sentinel, dtype chosen
        # from the measured diameter — core.packing, DESIGN.md §10) are what
        # HBM holds; every jit consumer below widens gathered rows in
        # registers.  The int32 scheme stays the host-side build artifact.
        if packed is None:
            packed = pack_labelling(scheme, lm_dist=jnp.asarray(lm_dist))
        self.packed = packed
        self._lm_dist = self.packed.lm_dist
        self.ctx = make_search_context(graph, scheme, backend=backend,
                                       packed=self.packed, **relay_kw)
        # Unmasked full-graph relay for the landmark-endpoint path (those
        # shortest paths may pass *through* landmarks, so G- is wrong there)
        # and for the update path's relabel.
        if full_engine is None:
            full_engine = make_relay(graph, backend=backend, **relay_kw)
        self._full_engine = full_engine
        # host copies of the pinned landmark set, handed on by each update
        self._is_landmark_np, self._lid_np = landmark_host or (
            np.asarray(scheme.is_landmark), np.asarray(scheme.lid))
        self._service = None

        self._search_batch = _make_search_batch(
            graph.n_vertices, max_levels, max_chain, use_pallas)

    @cached_property
    def _lm_dist_host(self) -> np.ndarray:
        """(R, V) int32 vertex-to-landmark distances on the host."""
        return np.asarray(self._lm_table, np.int32)

    @property
    def _unreached(self) -> np.ndarray:
        if self._unreached_mask is None:
            self._unreached_mask = (self._lm_dist_host >= INF).any(axis=0)
        return self._unreached_mask

    @cached_property
    def _rev_edge(self) -> np.ndarray:
        """Lazy: a built index's is an O(E log E) host sort, deferred to
        first query time; an updated index's comes from its mirror."""
        if self._slots is not None:
            return self._slots.rev_slots()
        return _reverse_edge_map(
            np.asarray(self.graph.src), np.asarray(self.graph.dst),
            self.graph.n_vertices)

    @cached_property
    def _rev_edge_j(self) -> jax.Array:
        return jnp.asarray(self._rev_edge)

    # -- per-lane device steps ----------------------------------------------

    def serve_step(self, us, vs):
        """The general lane: one fixed-shape query chunk through sketch +
        guided search + edge-mask symmetrization.  Takes int32 device/host
        arrays ``(us, vs)`` of any fixed shape (B,) and returns device
        arrays ``(dist (B,), edge_mask (B, E) bool)`` with no host sync
        (a ``GeneralChunk``, which also carries the recover job count).
        Public contract re-exported by ``repro.serving.make_spg_serve_step``;
        landmark-endpoint lanes are garbage here — the planner routes them
        to the landmark lane steps below."""
        d, m, jobs = self._search_batch(
            self.ctx, self.packed.label_dist, self.packed.meta_w,
            self.packed.meta_dist, us, vs,
        )
        return GeneralChunk(*_symmetrize(d, m, self._rev_edge_j), jobs)

    def landmark_pair_step(self, ru, rv):
        """Landmark-landmark lane step: (B,) landmark-index pairs ->
        device ``(dist (B,), edge_mask (B, E))``, label-only, no sync."""
        return _landmark_pair_lanes(
            self._lm_dist, self.packed.meta_dist,
            self.graph.src, self.graph.dst, self._rev_edge_j, ru, rv)

    def landmark_onesided_step(self, roots, r_idx):
        """One-sided landmark lane step: (B,) non-landmark roots + (B,)
        landmark indices -> device ``(dist (B,), edge_mask (B, E))``; one
        batched distance-bounded full-graph BFS, no sync."""
        return _landmark_onesided_lanes(
            self._full_engine, self._lm_dist,
            self.graph.src, self.graph.dst, self._rev_edge_j,
            roots, r_idx, max_levels=self.max_levels)

    # -- dynamic updates (DESIGN.md §13) -------------------------------------

    def apply_update(self, inserts=None, deletes=None, *,
                     churn_threshold: float = 0.5) -> "QbSIndex":
        """Apply one edge-update batch and return the index for the next
        epoch (``self`` is untouched — in-flight chunks pinned to it stay
        bit-consistent with their admission epoch).

        The batch merges into the host mirror of the edge slots
        (``graph.apply_edge_updates``), and the new CSR is uploaded once.
        The landmark set is pinned at epoch 0; labels are maintained by
        recomputing only the affected landmarks' BFS rows on the
        post-update graph, or the whole build past ``churn_threshold``
        (affected fraction of R), where the incremental path loses to a
        rebuild (``labelling.update_tables``).  Either way the new index's
        tables are bit-identical to a fresh build on the new graph with the
        same landmarks — the property-harness contract.

        An insert-only batch whose endpoints every landmark reaches, on
        tables in the narrowest packing, never waits for the device: its
        affected set is computed there (``labelling.affected_landmarks``),
        and no distance can grow, so the packing stays.  The new epoch's
        programs queue behind the chunks in flight, and the chunks admitted
        under it queue behind them.  Other batches read the affected set
        and the packing off host copies of the tables, which wait for the
        device.  ``last_update_info`` holds ``update_tables``' info
        (``labelling.update_path`` names its path) and whether the
        edge-slot capacity took a step (``warm_update_path``).
        """
        if self._slots is None:
            self._slots = EdgeSlots.of_graph(self.graph)
        n_v = self.graph.n_vertices
        slots, ins, dels = apply_edge_updates(self._slots, inserts, deletes)
        kw = dict(max_levels=self.max_levels, max_chain=self.max_chain,
                  chunk=self.chunk, use_pallas=self.use_pallas,
                  backend=self.backend, engine_opts=self._engine_opts,
                  epoch=self.epoch + 1)
        if not (ins.size or dels.size):   # nothing to apply: the same tables
            new = QbSIndex(self.graph, self.scheme, lm_dist=self._lm_table,
                           packed=self.packed, slots=self._slots,
                           unreached=self._unreached,
                           full_engine=self._full_engine,
                           landmark_host=(self._is_landmark_np, self._lid_np),
                           **kw)
            new.last_update_info = {"n_affected": 0, "full_rebuild": False,
                                    "capacity_step": False}
            return new
        ins = np.stack([ins // n_v, ins % n_v], axis=1)
        dels = np.stack([dels // n_v, dels % n_v], axis=1)
        graph_new = slots.graph()
        engine = make_relay(graph_new, backend=self.backend, host=slots.csr,
                            **self._engine_opts)
        aff = affected_landmarks(self.scheme, self._lm_table, ins, dels,
                                 slots.csr)
        scheme_new, lm_new, info = update_tables(
            engine, self.scheme, self._lm_table, aff,
            churn_threshold=churn_threshold)
        if (not dels.size and self.packed.dtype == np.uint8
                and not self._unreached[ins].any()):
            # distances only shrink where every landmark reached both ends
            dtype, unreached = self.packed.dtype, self._unreached
        else:
            lm_host = np.asarray(lm_new)
            dtype = choose_pack_dtype(scheme_new.label_dist, scheme_new.meta_w,
                                      scheme_new.meta_dist, lm_host)
            unreached = (lm_host >= INF).any(axis=0)
        new = QbSIndex(graph_new, scheme_new, lm_dist=lm_new,
                       packed=pack_tables(scheme_new, lm_new, dtype),
                       slots=slots, unreached=unreached, full_engine=engine,
                       landmark_host=(self._is_landmark_np, self._lid_np),
                       **kw)
        info["capacity_step"] = graph_new.n_edges != self.graph.n_edges
        new.last_update_info = info
        return new

    @property
    def n_live_slots(self) -> int:
        """Edge slots that hold an edge (the rest of ``graph.n_edges`` is
        padding the capacity ladder keeps free)."""
        if self._slots is not None:
            return self._slots.n_live
        return int(np.count_nonzero(
            np.asarray(self.graph.src) != np.asarray(self.graph.dst)))

    def warm_update_path(self, churn_threshold: float = 0.5) -> None:
        """Compile, and run once, every program the update path can take
        at this graph's edge-slot capacity: the device insert test, the
        table update (its three branches are one program) and the table
        packing at each packed dtype.  They run on this epoch's own tables
        with no landmark affected, and their results are dropped.  The
        serving tier calls it when an update takes a capacity step, before
        the new epoch serves (``ReplicaRouter.apply_update``,
        ``StreamingService.submit_update``), so no later update of the
        step compiles."""
        aff = affected_landmarks(self.scheme, self._lm_table,
                                 np.zeros((1, 2), np.int32),
                                 np.zeros((0, 2), np.int32), None)
        scheme, lm, _ = update_tables(self._full_engine, self.scheme,
                                      self._lm_table, aff,
                                      churn_threshold=churn_threshold)
        for dtype in (np.uint8, np.uint16):
            jax.block_until_ready(pack_tables(scheme, lm, dtype))

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, graph: Graph, n_landmarks: int = 20,
              landmarks: np.ndarray | None = None, sharded=None, **kw):
        """Build an index.  ``sharded=`` switches to the vertex-sharded
        variant (``core.sharded.ShardedIndex``): pass a
        ``jax.sharding.Mesh``, a device count, or ``True`` (all local
        devices) — labels are then *born* sharded on that mesh and every
        serving lane answers from the shards (DESIGN.md §11).  The
        sharded index takes its own serving knobs (``max_levels``,
        ``max_chain``, ``chunk``), not this class's backend/pallas ones."""
        if sharded is not None and sharded is not False:
            from .sharded import ShardedIndex
            mesh = None if sharded is True else sharded
            return ShardedIndex.build(
                graph, n_landmarks=n_landmarks, landmarks=landmarks,
                mesh=mesh, **kw)
        if landmarks is None:
            landmarks = select_landmarks(graph, n_landmarks)
        scheme = build_labelling(
            graph, landmarks, backend=kw.get("backend", "segment"),
            **(kw.get("engine_opts") or {}))
        return cls(graph, scheme, **kw)

    # -- queries (thin delegates over the planner/service) -------------------

    def make_service(self, **kw):
        """Construct a ``serving.ServingService`` over this index (async
        depth, result cache, multi-device mesh — see its docstring)."""
        from ..serving.service import ServingService
        return ServingService(self, **kw)

    def make_stream(self, *, policy=None, **kw):
        """Construct a ``serving.StreamingService``: queries arrive over
        time (``submit``/``drain``, per-query futures) and are coalesced
        into planner batches under a deadline/QoS-aware scheduler —
        adaptive chunk width, cross-batch dedup, cache-at-submit, and
        ``qos=`` classes with ``max_wait`` deadlines + weighted shares
        (DESIGN.md §5, §8).  ``kw`` passes through to the inner
        ``ServingService``."""
        from ..serving.stream import StreamingService
        return StreamingService(self, policy=policy, **kw)

    def _default_service(self):
        if self._service is None:
            self._service = self.make_service()
        return self._service

    def query_batch(self, us, vs) -> list[SPGResult]:
        return self._default_service().query_batch(us, vs)

    def query_batch_arrays(self, us, vs) -> tuple[np.ndarray, np.ndarray]:
        """Serving fast path: answer a query batch as raw arrays
        (dist (N,) int32, edge_mask (N, E) bool, symmetrized) with no
        per-query host objects."""
        return self._default_service().query_arrays(us, vs)

    def query(self, u: int, v: int) -> SPGResult:
        return self.query_batch([u], [v])[0]
