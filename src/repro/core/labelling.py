"""Algorithm 2 (labelling scheme construction), batched over landmarks.

The paper runs one BFS per landmark with two queues: ``Q_L`` (vertices
reached via a shortest path whose interior avoids all landmarks -> get a
label) and ``Q_N`` (reached only through some landmark -> no label).  We run
all |R| BFSs as a single level-synchronous frontier program over state

    depth[R, V]    BFS depth per landmark root (INF = unvisited)
    reach_L[R, V]  "exists a shortest path from root r whose interior
                    contains no landmark" (the Q_L membership bit)

Per level every edge relays two messages: *visited* (from any frontier
vertex) and *L* (only from frontier vertices allowed as path interior:
non-landmarks, or the root itself).  Q_L-before-Q_N priority at equal depth
in the paper is exactly the OR over same-level predecessors here.

Determinism (Lemma 5.2) is structural: the program never depends on a
landmark order, which is what licenses batching/vmapping the BFSs — the
TPU analogue of the paper's thread-level parallelism (§5.3).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .frontier import FrontierEngine, make_relay
from .graph import INF, Graph
from ..tracing import scope


class LabellingScheme(NamedTuple):
    """Labelling scheme L = (M, L) of Definition 4.2 in dense form."""

    landmarks: jax.Array    # (R,) int32 vertex ids
    lid: jax.Array          # (V,) int32 vertex -> landmark index, -1 otherwise
    is_landmark: jax.Array  # (V,) bool
    label_dist: jax.Array   # (V, R) int32; INF where no label entry exists
    meta_w: jax.Array       # (R, R) int32 meta-graph edge weights sigma; INF = no edge
    meta_dist: jax.Array    # (R, R) int32 APSP distances d_M on the meta-graph

    @property
    def n_landmarks(self) -> int:
        return int(self.landmarks.shape[0])

    def label_valid(self) -> jax.Array:
        return self.label_dist < INF

    def packed(self, lm_dist=None):
        """The packed-HBM view of this scheme (``core.packing``): uint8 or
        uint16 by measured diameter, dtype max as the INF sentinel.  The
        int32 arrays here stay the host-side build/oracle representation;
        serving reads the packed tables (``QbSIndex.packed``)."""
        from .packing import pack_labelling
        return pack_labelling(self, lm_dist=lm_dist)


def _bfs_rows(
    engine: FrontierEngine,
    roots: jax.Array,
    is_landmark: jax.Array,
    max_levels: int,
):
    """Level-synchronous (depth, reach_L) BFS rows from ``roots``.

    Each row is independent of the others (the frontier is per-row), so a
    subset of roots computes bit-identical rows to the full-R build — the
    property the incremental update path (``update_tables``) relies on.
    """
    K = roots.shape[0]
    V = engine.n_vertices

    depth0 = jnp.full((K, V), INF, jnp.int32).at[jnp.arange(K), roots].set(0)
    reach0 = jnp.zeros((K, V), bool).at[jnp.arange(K), roots].set(True)
    # roots may relay L-messages even though they are landmarks
    is_root = jnp.zeros((K, V), bool).at[jnp.arange(K), roots].set(True)
    propagate_ok = (~is_landmark)[None, :] | is_root

    def cond(carry):
        _, _, level, alive = carry
        return alive & (level < max_levels)

    def body(carry):
        depth, reach_l, level, _ = carry
        frontier = depth == level
        prop_l = frontier & reach_l & propagate_ok
        # one fused relay for both message kinds: the per-call fixed cost
        # dominates at small K (the incremental-update path), and rows are
        # independent so stacking changes nothing
        msg = engine.relay(jnp.concatenate([frontier, prop_l], axis=0))
        msg_vis, msg_l = msg[:K], msg[K:]
        new = msg_vis & (depth == INF)
        depth = jnp.where(new, level + 1, depth)
        reach_l = reach_l | (new & msg_l)
        return depth, reach_l, level + 1, new.any()

    depth, reach_l, _, _ = jax.lax.while_loop(
        cond, body, (depth0, reach0, jnp.int32(0), jnp.bool_(True))
    )
    return depth, reach_l


def _labelling_arrays(engine, landmarks, is_landmark, max_levels):
    R = landmarks.shape[0]
    depth, reach_l = _bfs_rows(engine, landmarks, is_landmark, max_levels)

    # Labels only for non-landmarks reached via a landmark-free path.
    valid = reach_l & (~is_landmark)[None, :]
    label_dist = jnp.where(valid, depth, INF).T.astype(jnp.int32)  # (V, R)

    # Meta edge (r_i, r_j) iff landmark j was reached from root i with the
    # L-bit set; weight = its BFS depth = d_G(r_i, r_j).
    at_land = depth[:, landmarks]          # (R, R)
    l_at_land = reach_l[:, landmarks]      # (R, R)
    meta_w = jnp.where(l_at_land, at_land, INF)
    meta_w = meta_w.at[jnp.arange(R), jnp.arange(R)].set(INF)  # no self edges
    # Determinism gives symmetry; enforce it to kill numeric asymmetry risk.
    meta_w = jnp.minimum(meta_w, meta_w.T)

    meta_dist = meta_apsp(meta_w)
    return label_dist, meta_w, meta_dist, depth


@partial(jax.jit, static_argnames=("max_levels",))
def _build_labelling_arrays(engine, landmarks, is_landmark, max_levels: int):
    return _labelling_arrays(engine, landmarks, is_landmark, max_levels)[:3]


def meta_apsp(meta_w: jax.Array) -> jax.Array:
    """Min-plus APSP (Floyd-Warshall) on the meta-graph. d_M == d_G between
    landmarks (meta edges are exact distances; every landmark-to-landmark
    shortest path splits at its interior landmarks into meta edges)."""
    R = meta_w.shape[0]
    d0 = jnp.minimum(meta_w, INF).at[jnp.arange(R), jnp.arange(R)].set(0)

    def body(k, d):
        cand = d[:, k][:, None] + d[k, :][None, :]
        return jnp.minimum(d, cand)

    d = jax.lax.fori_loop(0, R, body, d0)
    return jnp.minimum(d, INF)


def build_labelling(
    graph: Graph, landmarks: np.ndarray, *, max_levels: int = 256,
    backend: str = "segment", engine: FrontierEngine | None = None,
    **engine_kw,
) -> LabellingScheme:
    landmarks = jnp.asarray(landmarks, jnp.int32)
    R = int(landmarks.shape[0])
    V = graph.n_vertices
    is_landmark = jnp.zeros((V,), bool).at[landmarks].set(True)
    lid = jnp.full((V,), -1, jnp.int32).at[landmarks].set(jnp.arange(R, dtype=jnp.int32))
    if engine is None:
        engine = make_relay(graph, backend=backend, **engine_kw)
    label_dist, meta_w, meta_dist = _build_labelling_arrays(
        engine, landmarks, is_landmark, max_levels)
    return LabellingScheme(
        landmarks=landmarks,
        lid=lid,
        is_landmark=is_landmark,
        label_dist=label_dist,
        meta_w=meta_w,
        meta_dist=meta_dist,
    )


# ---------------------------------------------------------------------------
# Incremental maintenance (DESIGN.md §13): affected-landmark recompute.
# ---------------------------------------------------------------------------


INSERT_BLOCK = 64    # inserts per call of the device insert test (one shape)


@jax.jit
def _inserts_affected(label_dist, meta_w, lm_dist, lid, is_landmark, ins, aff):
    """``aff`` or the landmarks that ``affected_landmarks``' insert
    criteria flag for the inserts ``ins`` (B, 2), read off the device
    tables.  A ``(0, 0)`` pad row flags nothing (equal depths)."""
    with scope("qbs.update"):
        R = lm_dist.shape[0]
        u, v = ins[:, 0], ins[:, 1]
        du, dv = lm_dist[:, u], lm_dist[:, v]              # (R, B)
        gap = jnp.abs(du - dv)

        def bits(x):
            """(R, B) reach_L and live L-contribution of the endpoints x."""
            own = jnp.arange(R)[:, None] == lid[x][None, :]   # a root's own bit
            labelled = (label_dist[x, :] < INF).T
            lm_x = is_landmark[x][None, :]
            reach = jnp.where(lm_x, (meta_w[:, lid[x]] < INF) | own, labelled)
            return reach, jnp.where(lm_x, own, labelled)

        (ru, cu), (rv, cv) = bits(u), bits(v)
        tie = (gap == 1) & jnp.where(du < dv, cu & ~rv, cv & ~ru)
        return aff | ((gap >= 2) | tie).any(axis=1)


def affected_landmarks(
    scheme: LabellingScheme,
    lm_dist,
    inserts: np.ndarray,
    deletes: np.ndarray,
    csr: tuple[np.ndarray, np.ndarray, np.ndarray],
) -> jax.Array:
    """``(R,)`` bool device mask of landmarks whose BFS row an update batch
    touches.

    ``lm_dist`` is the exact pre-update ``(R, V)`` int32 distance table
    (where it lives), ``inserts``/``deletes`` the *effective* delta
    (insert-of-absent / delete-of-present edges only —
    ``QbSIndex.apply_update`` filters) and ``csr`` the post-batch graph's
    ``(indptr, src, dst)`` on the host.  Per landmark r and edge (a, b)
    with a the endpoint nearer r, the criteria are exact, not heuristic:

    * ``|d(r,a) - d(r,b)| >= 2`` insert: distances shorten — affected.
    * equal depths (or both INF): the edge joins or leaves no shortest
      path from r (any path through it is strictly longer) — unchanged.
    * ``diff == 1`` insert: depths are unchanged (the new path ties);
      only the shortest-path DAG gains the edge a -> b, whose reach_L
      contribution is ``reach_L(a) & propagate_ok(a)``.  The row changes
      only if that contribution is live *and* b lacked the L-bit:
      ``reach_L(a) & ok(a) & ~reach_L(b)``.
    * ``diff == 1`` delete: affected if b loses its last surviving
      shortest predecessor (checked against ``graph_new``'s CSR — so two
      deletes in one batch cannot alibi each other), or if the removed
      DAG edge carried a live L-contribution into a reached b *and* no
      surviving predecessor still contributes one:
      ``reach_L(a) & ok(a) & reach_L(b) & ~l_keep(b)``.

    reach_L is read off the existing tables: ``label_dist[x, r] < INF``
    for non-landmark x, ``meta_w[r, lid[x]] < INF`` for landmark x (row
    r's own root is True); ``propagate_ok`` is false exactly for
    non-root landmarks, which never relay L-messages.  Label sparsity is
    what makes this tight on hub-heavy graphs: most diff==1 edges hang
    off landmark-shadowed vertices and flag nothing.

    The insert criteria run on the device, in blocks of
    ``INSERT_BLOCK`` (``_inserts_affected``): an insert-only batch copies
    nothing to the host and waits for nothing.  The delete criterion
    reads CSR rows, so it runs on the host, from host copies of the
    tables.

    Batch-exactness: if no edge flags row r, induction over depth levels
    shows the BFS depth table and then the reach_L fixpoint of row r are
    preserved edge-by-edge (every insert ties or lands on a dead
    contribution, every delete leaves a supporting predecessor and
    removes only dead or redundant contributions).  Flagged rows are
    recomputed exactly on ``graph_new``.
    """
    aff = np.zeros((scheme.n_landmarks,), bool)
    if len(deletes):
        aff = _deletes_affected(scheme, np.asarray(lm_dist), deletes, csr)
    ins = np.asarray(inserts, np.int32).reshape(-1, 2)
    ins = np.concatenate([ins, np.zeros((-len(ins) % INSERT_BLOCK, 2), np.int32)])
    aff = jnp.asarray(aff)
    for lo in range(0, len(ins), INSERT_BLOCK):
        aff = _inserts_affected(scheme.label_dist, scheme.meta_w, lm_dist,
                                scheme.lid, scheme.is_landmark,
                                jnp.asarray(ins[lo:lo + INSERT_BLOCK]), aff)
    return aff


def _deletes_affected(scheme: LabellingScheme, lm: np.ndarray, deletes,
                      csr) -> np.ndarray:
    """``affected_landmarks``' delete criterion on host copies of the
    tables: the ``(R,)`` bool mask."""
    R = lm.shape[0]
    aff = np.zeros((R,), bool)
    label = np.asarray(scheme.label_dist)        # (V, R)
    meta_w = np.asarray(scheme.meta_w)           # (R, R)
    lid = np.asarray(scheme.lid)
    is_lm = np.asarray(scheme.is_landmark)
    indptr, _, dst = csr

    def reach(x: int) -> np.ndarray:
        """(R,) reach_L[r, x]: a landmark-interior-free shortest r-x path."""
        if is_lm[x]:
            out = meta_w[:, lid[x]] < INF
            out[lid[x]] = True                   # own root
            return out
        return label[x, :] < INF

    def contrib(x: int) -> np.ndarray:
        """(R,) live L-contribution of x: reach_L & propagate_ok."""
        if is_lm[x]:
            out = np.zeros((R,), bool)
            out[lid[x]] = True                   # roots relay their own bit
            return out
        return label[x, :] < INF

    def _pred_keep(x: int, dx: np.ndarray):
        """For farther endpoint x: (R,) has-surviving-shortest-predecessor
        and (R,) some survivor still carries a live L-contribution."""
        nb = dst[indptr[x]:indptr[x + 1]]
        nb = nb[nb != x]                         # drop self-loop padding
        if not nb.size:
            z = np.zeros((R,), bool)
            return z, z
        at_depth = lm[:, nb] == (dx - 1)[:, None]        # (R, deg)
        contrib_nb = (label[nb, :] < INF).T              # (R, deg)
        lm_nb = np.nonzero(is_lm[nb])[0]
        for j in lm_nb:                                  # landmark neighbors:
            contrib_nb[lid[nb[j]], j] = True             # roots relay own bit
        return at_depth.any(axis=1), (at_depth & contrib_nb).any(axis=1)

    for u, v in np.asarray(deletes, np.int64).reshape(-1, 2).tolist():
        du, dv = lm[:, u], lm[:, v]
        one = np.abs(du - dv) == 1               # real edges: gap <= 1
        if not one.any():
            continue
        cu, cv, ru, rv = contrib(u), contrib(v), reach(u), reach(v)
        a_is_u = du < dv
        pred_u, keep_u = _pred_keep(u, du)
        pred_v, keep_v = _pred_keep(v, dv)
        orphaned = np.where(a_is_u, ~pred_v, ~pred_u)
        l_loss = np.where(a_is_u, cu & rv & ~keep_v, cv & ru & ~keep_u)
        aff |= one & (orphaned | l_loss)
    return aff


def row_widths(k_max: int) -> tuple[int, ...]:
    """The widths an affected set of 1..``k_max`` landmarks is padded to:
    {1, 2, 3, 4, 6, 8, 12, ...} (powers of two and their 1.5x midpoints,
    two shapes an octave) capped at ``k_max``, so a relabel's cost follows
    the affected count with few shapes."""
    def pad(k):
        p = 1 << (k - 1).bit_length()
        return p // 4 * 3 if k <= p // 4 * 3 else p
    return tuple(sorted({min(pad(k), k_max) for k in range(1, k_max + 1)}))


@partial(jax.jit, static_argnames=("k_max", "max_levels"))
def _update_tables(engine, landmarks, is_landmark, label_dist, meta_w,
                   meta_dist, lm_dist, aff, k_max: int, max_levels: int):
    """One epoch's tables on ``engine``'s graph: the old ones where no
    landmark is affected, the affected rows recomputed where at most
    ``k_max`` are (padded to a ``row_widths`` width with a duplicate,
    which recomputes identical values), the whole build past that.  One
    program a graph shape: ``lax.switch`` runs one branch."""
    R = landmarks.shape[0]
    widths = row_widths(k_max)
    with scope("qbs.update"):
        n = aff.sum(dtype=jnp.int32)

        def keep():
            return label_dist, meta_w, meta_dist, lm_dist

        def rows(w):
            idx = jnp.nonzero(aff, size=w, fill_value=R)[0]
            idx = jnp.where(idx == R, idx[0], idx)
            with scope("qbs.update.relabel"):
                depth, reach_l = _bfs_rows(engine, landmarks[idx], is_landmark,
                                           max_levels)
            valid = reach_l & (~is_landmark)[None, :]
            label = label_dist.at[:, idx].set(jnp.where(valid, depth, INF).T)
            # Raw meta values are symmetric (reach_L is a symmetric
            # property), and an entry (i, j) only changes when d(r_i, r_j)
            # or its L-bit moves, which flags *both* rows.  So scattering
            # the recomputed rows into both the rows and the columns of the
            # affected set, resetting its diagonal to INF and re-harmonizing
            # with the transpose reproduces the fresh build's meta_w.
            raw = jnp.where(reach_l[:, landmarks], depth[:, landmarks], INF)
            mw = meta_w.at[idx, :].set(raw).at[:, idx].set(raw.T)
            mw = mw.at[idx, idx].set(INF)
            mw = jnp.minimum(mw, mw.T)
            return label, mw, meta_apsp(mw), lm_dist.at[idx].set(depth)

        def full():
            with scope("qbs.update.relabel"):
                return _labelling_arrays(engine, landmarks, is_landmark,
                                         max_levels)

        def int32(fn, *a):
            return lambda: tuple(jnp.asarray(t, jnp.int32) for t in fn(*a))

        branch = jnp.where(n == 0, 0, jnp.where(
            n > k_max, len(widths) + 1,
            1 + jnp.searchsorted(jnp.asarray(widths, jnp.int32), n)))
        tables = jax.lax.switch(
            branch, [int32(keep), *(int32(rows, w) for w in widths), int32(full)])
        return tables, n, n > k_max


def update_tables(engine: FrontierEngine, scheme: LabellingScheme, lm_dist,
                  aff, *, churn_threshold: float = 0.5,
                  max_levels: int = 256) -> tuple[LabellingScheme, jax.Array, dict]:
    """Maintain a labelling across one edge-update batch on the device.

    ``aff`` is the batch's ``(R,)`` affected-landmark mask
    (``affected_landmarks``) and ``engine`` relays over the post-update
    graph.  Returns
    ``(scheme_new, lm_dist_new, info)``, both tables bit-identical to a
    fresh ``build_labelling`` on that graph (the property-harness
    contract), all device arrays: nothing waits for the device.  Where
    more than ``churn_threshold`` of the landmarks are affected the
    incremental path loses to a rebuild and the program rebuilds.
    ``info`` holds ``affected``, ``n_affected`` and ``full_rebuild`` as
    device values; ``update_path`` names the path."""
    R = scheme.n_landmarks
    k_max = max(0, min(R, int(churn_threshold * R)))
    aff = jnp.asarray(aff, bool)
    (label, mw, md, lm), n, full = _update_tables(
        engine, scheme.landmarks, scheme.is_landmark, scheme.label_dist,
        scheme.meta_w, scheme.meta_dist, jnp.asarray(lm_dist, jnp.int32), aff,
        k_max, max_levels)
    return (scheme._replace(label_dist=label, meta_w=mw, meta_dist=md), lm,
            {"affected": aff, "n_affected": n, "full_rebuild": full})


def update_path(info: dict) -> str:
    """How an epoch's labels were made, from ``update_tables``' ``info``:
    ``"noop"`` (no landmark's row moved; the tables are the old ones),
    ``"incremental"`` (the affected rows recomputed) or ``"rebuild"``.
    Waits for the device where the values are still being computed."""
    if not int(info["n_affected"]):
        return "noop"
    return "rebuild" if bool(info["full_rebuild"]) else "incremental"


def labelling_size_bytes(scheme: LabellingScheme) -> dict:
    """Paper's size accounting (§6.1): |R| * 8 bits per vertex for L, plus
    the meta-graph.  Distances on complex networks fit 8 bits — which is
    no longer aspirational: ``packing.packed_size_bytes`` measures the
    bytes the packed tables actually occupy in HBM."""
    v = int(scheme.label_dist.shape[0])
    r = scheme.n_landmarks
    n_meta = int(np.asarray((scheme.meta_w < INF).sum()))
    return {
        "label_bytes": v * r,                # 8 bits per (vertex, landmark)
        "meta_bytes": n_meta * (4 + 1),      # (pair id, weight)
        "n_meta_edges": n_meta,
    }
