"""Pluggable frontier engine: the one level-synchronous BFS relay shared by
every phase of QbS (DESIGN.md §3).

Every phase of the system — offline labelling (Algorithm 2), the online
sketch-bounded bidirectional search, the reverse/recover sweeps
(Algorithm 4), and the Bi-BFS / full-BFS baselines — is the same
operation: propagate per-edge boolean messages into their destination
vertices,

    next[k, w] = OR_{e : dst[e] = w}  values[k, src[e]] & mask[e]

The edge list is symmetric (every edge in both orientations, and any baked
mask with it), so the same OR runs over each vertex's own CSR row:

    next[k, w] = OR_{e : src[e] = w}  values[k, dst[e]] & mask[e]

This module owns that operation behind pluggable backends:

* ``segment``  — the default: a *row pull* over the src-sorted edge list
                 (``graph.from_edges`` sorts it and keeps ``indptr``): gather
                 ``values[:, dst]`` in CSR order and OR each contiguous row
                 with a blocked prefix count (``_row_or``), no scatter op.
* ``csr``      — pull formulation over the CSR (src-sorted) edge layout:
                 ``next[w] = OR_{e in row w} values[dst[e]]``, valid because
                 the graph and any baked edge mask are symmetric.  The
                 segment ids are the *sorted* ``src`` array, so the
                 reduction runs over contiguous segments; an optional
                 ``block_size`` processes the edge list in fixed-size blocks
                 to bound the (K, E) message temporary.
* ``hybrid``   — degree-split hub/tail relay: the dense hub-hub block (where
                 traversal work concentrates on complex networks, §6.5 of
                 the paper) runs as an OR-AND matmul — the MXU-native
                 ``kernels.frontier.bitmap_expand`` on TPU, the same math as
                 a jnp f32 matmul elsewhere — while the sparse tail keeps
                 the ``segment_max`` relay over a *compacted* tail edge
                 list.  Results are OR-ed.  Bit-identical to ``segment`` for
                 symmetric graphs.

Edge masks that are static per index (the G- mask ``gminus_e``) are baked
in at build time: ``hybrid`` folds them into the dense block and the tail
compaction, so the per-level relay carries no mask traffic at all.

The engine is a registered pytree (arrays are leaves; backend/shape config
is static aux data), so it passes through ``jit`` / ``vmap(in_axes=None)``
/ ``shard_map`` like any other per-graph constant, and jit caches key on
the static config.

``segment_or`` is the raw primitive; the edge-sharded shard_map programs in
``core.distributed`` / ``core.scale_serve`` call it directly on their local
edge shards so the relay semantics live in exactly one module.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import INF, Graph
from .packing import pack_bits, unpack_bits

BACKENDS = ("segment", "csr", "hybrid")


def segment_or(
    messages: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    *,
    acc_dtype=jnp.int32,
) -> jax.Array:
    """OR-reduce per-edge boolean messages ``(K, E)`` into ``(K, N)``.

    The canonical frontier-relay reduction: booleans accumulate through an
    integer ``segment_max`` (order-invariant, hence safe to reorder, shard
    and block).  ``acc_dtype`` only changes the accumulator width (the
    shard_map programs use int8 to shrink on-device temporaries); the
    boolean result is identical for any width.
    """
    acc = jax.ops.segment_max(
        messages.astype(acc_dtype).T, segment_ids, num_segments=num_segments
    )
    return (acc > 0).T


# Edges per block of the row reduction: in-block prefix counts stay below
# 256, and bf16 holds every integer up to 256 exactly, so the triangular
# matmul below is exact in and out.
ROW_BLOCK = 256


def _row_or(messages: jax.Array, indptr: jax.Array) -> jax.Array:
    """OR ``(K, E)`` boolean messages over the contiguous rows
    ``[indptr[w], indptr[w + 1])``: ``(K, V)``, no scatter.

    A row holds a message iff the count of messages before its end exceeds
    the count before its start.  The counts come blocked: an exclusive
    prefix inside each block of ``ROW_BLOCK`` edges is one triangular 0/1
    matmul on the MXU, and the blocks' offsets one short cumulative sum
    over ``E / ROW_BLOCK`` totals; only the ``V + 1`` row boundaries are
    read back."""
    k, e = messages.shape
    nb = e // ROW_BLOCK + 1              # one slot past E, so indptr[V] fits
    m = jnp.pad(messages, ((0, 0), (0, nb * ROW_BLOCK - e)))
    m = m.astype(jnp.bfloat16).reshape(k, nb, ROW_BLOCK)
    i = jnp.arange(ROW_BLOCK)
    before = (i[:, None] < i[None, :]).astype(jnp.bfloat16)
    excl = jnp.einsum("knb,bc->knc", m, before,
                      preferred_element_type=jnp.bfloat16)
    tot = excl[..., -1].astype(jnp.int32) + m[..., -1].astype(jnp.int32)
    offs = jnp.cumsum(tot, axis=1) - tot
    at = (offs[:, indptr // ROW_BLOCK]
          + excl.reshape(k, -1)[:, indptr].astype(jnp.int32))
    return at[:, 1:] != at[:, :-1]


def _dense_or_matmul(frontier: jax.Array, adjacency: jax.Array) -> jax.Array:
    """next[k, j] = OR_i frontier[k, i] & adjacency[i, j] via an f32 matmul
    (the same OR-AND-semiring-on-MXU math as ``bitmap_expand``)."""
    acc = jnp.dot(
        frontier.astype(jnp.float32),
        adjacency.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return acc > 0.5


@jax.tree_util.register_pytree_node_class
class FrontierEngine:
    """Per-graph relay engine.  Arrays are pytree leaves; everything else is
    static aux data (part of the jit cache key)."""

    def __init__(
        self,
        arrays: dict[str, Any],
        *,
        backend: str,
        n_vertices: int,
        n_edges: int,
        block_size: int = 0,
        use_pallas: bool = False,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
        self.arrays = arrays
        self.backend = backend
        self.n_vertices = n_vertices
        self.n_edges = n_edges
        self.block_size = block_size
        self.use_pallas = use_pallas

    # -- pytree protocol -----------------------------------------------------

    def tree_flatten(self):
        keys = tuple(sorted(self.arrays))
        children = tuple(self.arrays[k] for k in keys)
        aux = (keys, self.backend, self.n_vertices, self.n_edges,
               self.block_size, self.use_pallas)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, backend, n_v, n_e, block, pallas = aux
        return cls(dict(zip(keys, children)), backend=backend, n_vertices=n_v,
                   n_edges=n_e, block_size=block, use_pallas=pallas)

    # -- the one operation ---------------------------------------------------

    def relay(self, values: jax.Array) -> jax.Array:
        """Frontier relay: ``(K, V) -> (K, V)`` (or ``(V,) -> (V,)``) with
        the build-time edge mask applied.  next[k, w] = OR over unmasked
        edges (x, w) of values[k, x]."""
        squeeze = values.ndim == 1
        f = values[None] if squeeze else values
        if self.backend == "segment":
            out = self._relay_segment(f)
        elif self.backend == "csr":
            out = self._relay_csr(f)
        else:  # constructor validated membership in BACKENDS
            out = self._relay_hybrid(f)
        return out[0] if squeeze else out

    def pull(self, values: jax.Array,
             edge_mask: jax.Array | None = None) -> jax.Array:
        """Row pull ``(K, V) -> (K, V)`` (or ``(V,) -> (V,)``) with a
        per-edge condition: next[k, w] = OR over edges e of row w
        (``src[e] == w``) of ``values[k, dst[e]] & mask[e] &
        edge_mask[k, e]``, where ``mask`` is the build-time edge mask and
        ``edge_mask`` is ``(E,)`` or ``(K, E)`` over the original edge list.
        Messages that do not factor into per-vertex values (the recover
        chain's label-decrement coupling) relay through here; it reads the
        edge list on every backend, because a dense block cannot represent
        arbitrary per-edge conditions."""
        squeeze = values.ndim == 1
        f = values[None] if squeeze else values
        msgs = f[:, self.arrays["dst"]]
        mask = self.arrays.get("mask")
        if mask is not None:
            msgs = msgs & mask
        if edge_mask is not None:
            msgs = msgs & edge_mask
        out = _row_or(msgs, self.arrays["indptr"])
        return out[0] if squeeze else out

    # -- backends ------------------------------------------------------------

    def _relay_segment(self, f: jax.Array) -> jax.Array:
        # symmetric edge list and mask: the in-edges of w are its own row
        return self.pull(f)

    def _relay_csr(self, f: jax.Array) -> jax.Array:
        # Pull over the src-sorted (CSR-row) layout: by edge-set and mask
        # symmetry, OR over out-neighbours == OR over in-neighbours.
        gather = self.arrays["csr_gather"]   # dst column, padded to blocks
        key = self.arrays["csr_key"]         # sorted src, pad rows -> V
        mask = self.arrays.get("csr_mask")
        v = self.n_vertices
        if not self.block_size:
            msgs = f[:, gather]
            if mask is not None:
                msgs = msgs & mask
            return segment_or(msgs, key, v + 1)[:, :v]

        b = self.block_size
        nb = gather.shape[0] // b
        k = f.shape[0]

        def body(i, acc):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=i * b, slice_size=b)
            msgs = f[:, sl(gather)]
            if mask is not None:
                msgs = msgs & sl(mask)
            blk = segment_or(msgs, sl(key), v + 1)
            return acc | blk

        acc0 = jnp.zeros((k, v + 1), bool)
        return jax.lax.fori_loop(0, nb, body, acc0)[:, :v]

    def _relay_hybrid(self, f: jax.Array) -> jax.Array:
        hub_ids = self.arrays["hub_ids"]
        # hub-hub reachability rows live bit-packed in HBM (32 columns per
        # uint32 word, core.packing layout); the Pallas kernel unpacks word
        # tiles in VMEM and the matmul fallback unpacks inside this program
        # — the dense (H, H) mask never persists in HBM
        adj_words = self.arrays["adj_hh_words"]
        h = hub_ids.shape[0]
        tail_src = self.arrays.get("tail_src")
        if tail_src is not None:
            out = segment_or(f[:, tail_src], self.arrays["tail_dst"],
                             self.n_vertices)
        else:
            out = jnp.zeros((f.shape[0], self.n_vertices), bool)
        f_h = f[:, hub_ids]
        if self.use_pallas:
            from ..kernels.frontier import bitmap_expand_packed
            next_h = bitmap_expand_packed(f_h, adj_words, n_cols=h)
        else:
            next_h = _dense_or_matmul(f_h, unpack_bits(adj_words, h))
        return out.at[:, hub_ids].set(out[:, hub_ids] | next_h)


@functools.partial(jax.jit, static_argnames=("max_levels",))
def bfs_depths(engine: FrontierEngine, root: jax.Array, max_levels: int,
               bound: jax.Array | None = None) -> jax.Array:
    """Level-synchronous single-source BFS over the engine's graph:
    ``(V,)`` int32 depths, ``INF`` = unreached.  ``bound`` (traced)
    optionally truncates the expansion at that depth — the landmark-endpoint
    serving path explores only the ball certificates need.  The one BFS
    driver shared by the oracle/baseline BFSs and the serving fallbacks."""
    depth0 = jnp.full((engine.n_vertices,), INF, jnp.int32).at[root].set(0)

    def cond(c):
        _, level, alive = c
        more = alive & (level < max_levels)
        if bound is not None:
            more = more & (level < bound)
        return more

    def body(c):
        depth, level, _ = c
        msg = engine.relay(depth == level)
        new = msg & (depth == INF)
        return jnp.where(new, level + 1, depth), level + 1, new.any()

    depth, _, _ = jax.lax.while_loop(
        cond, body, (depth0, jnp.int32(0), jnp.bool_(True)))
    return depth


@functools.partial(jax.jit, static_argnames=("max_levels",))
def bfs_depths_batch(engine: FrontierEngine, roots: jax.Array, max_levels: int,
                     bounds: jax.Array | None = None) -> jax.Array:
    """Batched level-synchronous BFS: ``(B,)`` roots -> ``(B, V)`` int32
    depths, ``INF`` = unreached.  One engine relay per level serves every
    row at once (the relay is row-independent for all backends), so a lane
    of B sources costs the same number of device programs as one.

    ``bounds`` (traced ``(B,)``) truncates each row independently at its own
    depth, exactly like ``bfs_depths``'s scalar ``bound``: row k expands
    only while ``level < bounds[k]``.  Rows are bit-identical to running
    ``bfs_depths`` per root with the matching bound — the batched form of
    the landmark-endpoint serving lane (see ``serving.planner``)."""
    b = roots.shape[0]
    depth0 = jnp.full((b, engine.n_vertices), INF, jnp.int32)
    depth0 = depth0.at[jnp.arange(b), roots].set(0)

    def active_rows(level, alive):
        act = alive & (level < max_levels)
        if bounds is not None:
            act = act & (level < bounds)
        return act

    def cond(c):
        _, level, alive = c
        return active_rows(level, alive).any()

    def body(c):
        depth, level, alive = c
        act = active_rows(level, alive)
        frontier = (depth == level) & act[:, None]
        msg = engine.relay(frontier)
        new = msg & (depth == INF)
        alive = jnp.where(act, new.any(axis=1), alive)
        return jnp.where(new, level + 1, depth), level + 1, alive

    depth, _, _ = jax.lax.while_loop(
        cond, body, (depth0, jnp.int32(0), jnp.ones((b,), bool)))
    return depth


class HubSplit(NamedTuple):
    """Host-side degree split (see ``Graph.hub_split``)."""

    hub_ids: np.ndarray    # (H,) int32, ascending vertex ids
    is_hub: np.ndarray     # (V,) bool
    hub_pos: np.ndarray    # (V,) int64 vertex -> hub-block row, -1 otherwise
    adj_hh: np.ndarray     # (H, H) bool dense hub-hub adjacency
    hub_edge: np.ndarray   # (E,) bool: both endpoints are hubs (excl. loops)


def hub_split(graph: Graph, n_hubs: int | None = None) -> HubSplit:
    """Split vertices by degree: the top-``n_hubs`` vertices (self-loop edge
    padding excluded from the degree count) become the dense hub block."""
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    v = graph.n_vertices
    real = src != dst
    deg = np.zeros((v,), np.int64)
    np.add.at(deg, src[real], 1)
    h = min(v, 128 if n_hubs is None else n_hubs)
    h = max(h, 1)
    order = np.argsort(-deg, kind="stable")
    hub_ids = np.sort(order[:h]).astype(np.int32)
    is_hub = np.zeros((v,), bool)
    is_hub[hub_ids] = True
    hub_pos = np.full((v,), -1, np.int64)
    hub_pos[hub_ids] = np.arange(h)
    hub_edge = real & is_hub[src] & is_hub[dst]
    adj = np.zeros((h, h), bool)
    adj[hub_pos[src[hub_edge]], hub_pos[dst[hub_edge]]] = True
    return HubSplit(hub_ids, is_hub, hub_pos, adj, hub_edge)


def make_relay(
    graph: Graph,
    *,
    backend: str = "segment",
    edge_mask: np.ndarray | jax.Array | None = None,
    n_hubs: int | None = None,
    block_size: int = 0,
    use_pallas: bool | None = None,
    host: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None,
) -> FrontierEngine:
    """Build a ``FrontierEngine`` for ``graph``.

    ``edge_mask`` is a *static* per-edge boolean (the G- mask); it must be
    symmetric (``mask[e] == mask[rev(e)]``), which holds for any mask of the
    form ``f[src] & f[dst]`` on the symmetrized edge list.  The relays
    pull over CSR rows, so the edge set itself must be symmetric and
    sorted by ``src`` with ``graph.indptr`` its row bounds, as
    ``graph.from_edges`` leaves it; an edge list out of ``src`` order is
    refused.  Build is host-side (numpy); ``host`` is ``graph``'s
    ``(indptr, src, dst)`` on the host where the caller holds them (the
    update path's mirror), so they are not copied back from the device.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    v, e = graph.n_vertices, graph.n_edges
    src_np, dst_np = host[1:] if host is not None else (
        np.asarray(graph.src), np.asarray(graph.dst))
    if np.any(src_np[:-1] > src_np[1:]):
        raise ValueError("edge list is not sorted by src; build the graph "
                         "with graph.from_edges")

    arrays: dict[str, Any] = {"src": graph.src, "dst": graph.dst,
                              "indptr": graph.indptr}
    if edge_mask is not None:
        arrays["mask"] = jnp.asarray(edge_mask, bool)

    if backend == "segment":
        return FrontierEngine(arrays, backend=backend, n_vertices=v, n_edges=e)
    mask_np = None if edge_mask is None else np.asarray(edge_mask).astype(bool)

    if backend == "csr":
        gather = dst_np
        key = src_np
        m = mask_np
        if block_size:
            pad = (-e) % block_size
            if pad:
                gather = np.concatenate([gather, np.zeros((pad,), np.int32)])
                key = np.concatenate([key, np.full((pad,), v, np.int32)])
                if m is not None:
                    m = np.concatenate([m, np.zeros((pad,), bool)])
        arrays["csr_gather"] = jnp.asarray(gather)
        arrays["csr_key"] = jnp.asarray(key)
        if m is not None:
            arrays["csr_mask"] = jnp.asarray(m)
        return FrontierEngine(arrays, backend=backend, n_vertices=v,
                              n_edges=e, block_size=block_size)

    # hybrid: degree split, dense hub block (mask baked in), compacted tail
    split = hub_split(graph, n_hubs)
    adj = split.adj_hh.copy()
    keep_tail = ~split.hub_edge
    if mask_np is not None:
        dead = split.hub_edge & ~mask_np
        adj[split.hub_pos[src_np[dead]], split.hub_pos[dst_np[dead]]] = False
        keep_tail = keep_tail & mask_np
    arrays["hub_ids"] = jnp.asarray(split.hub_ids)
    # store the hub-hub block bit-packed end-to-end (uint32 words); both
    # relay paths unpack on the fly (_relay_hybrid)
    arrays["adj_hh_words"] = pack_bits(jnp.asarray(adj))
    if keep_tail.any():
        arrays["tail_src"] = jnp.asarray(src_np[keep_tail])
        arrays["tail_dst"] = jnp.asarray(dst_np[keep_tail])
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    return FrontierEngine(arrays, backend=backend, n_vertices=v, n_edges=e,
                          use_pallas=bool(use_pallas))


def abstract_engine(n_vertices: int, n_edges: int, *,
                    masked: bool = False) -> FrontierEngine:
    """ShapeDtypeStruct-only ``segment`` engine for ``.lower()`` dry-runs at
    paper scale (no allocation; see ``launch.dryrun``)."""
    i32 = jnp.int32
    arrays: dict[str, Any] = {
        "src": jax.ShapeDtypeStruct((n_edges,), i32),
        "dst": jax.ShapeDtypeStruct((n_edges,), i32),
        "indptr": jax.ShapeDtypeStruct((n_vertices + 1,), i32),
    }
    if masked:
        arrays["mask"] = jax.ShapeDtypeStruct((n_edges,), jnp.bool_)
    return FrontierEngine(arrays, backend="segment", n_vertices=n_vertices,
                          n_edges=n_edges)
