"""QbS query-serving driver: build (or load) a labelling scheme for a graph
and answer batched shortest-path-graph queries.

  PYTHONPATH=src python -m repro.launch.serve --graph ba --n 20000 \
      --landmarks 20 --queries 200

``--shards N`` builds the vertex-sharded index instead (labels born
sharded over an N-device mesh, every lane served from the shards —
DESIGN.md §11); emulate devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--replicas N`` serves through the consistent-hash replica tier
(``serving.ReplicaRouter`` — DESIGN.md §12) instead of the bare index,
and ``--metrics-port P`` (0 = ephemeral) exports every replica's
counters and per-QoS latency histograms as a Prometheus-style text
endpoint at ``http://127.0.0.1:P/metrics`` while queries run.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..core import (
    QbSIndex,
    barabasi_albert_graph,
    gnp_random_graph,
    labelling_size_bytes,
    packed_size_bytes,
    ring_of_cliques,
)
from .compile_cache import configure_compile_cache


def build_graph(kind: str, n: int, seed: int):
    if kind == "ba":
        return barabasi_albert_graph(n, 3, seed=seed)
    if kind == "gnp":
        return gnp_random_graph(n, 6.0, seed=seed)
    if kind == "cliques":
        return ring_of_cliques(max(n // 8, 2), 8, seed=seed)
    raise ValueError(kind)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="ba", choices=["ba", "gnp", "cliques"])
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--landmarks", type=int, default=20)
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=0,
                    help="build the vertex-sharded index over this many "
                         "devices (0 = replicated single-device index)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through a consistent-hash ReplicaRouter "
                         "over this many streaming replicas (0 = direct "
                         "index serving)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="export the metrics scrape endpoint on this port "
                         "(0 = pick an ephemeral port); implies at least "
                         "one streaming replica")
    args = ap.parse_args()
    print(f"[serve] compilation cache: {configure_compile_cache()}")

    g = build_graph(args.graph, args.n, args.seed)
    print(f"[serve] graph {args.graph}: V={g.n_vertices} E={g.n_edges // 2}")

    t0 = time.perf_counter()
    if args.shards:
        idx = QbSIndex.build(g, n_landmarks=args.landmarks,
                             chunk=args.chunk, sharded=args.shards)
        t1 = time.perf_counter()
        info = idx.sharded_size_bytes()
        print(f"[serve] sharded labelling built in {t1 - t0:.2f}s over "
              f"{info['n_shards']} devices ({idx.labels.pack_dtype})")
        print(f"[serve] per-device bytes: "
              f"{info['per_device_bytes'] / 1e6:.2f}MB "
              f"(labels {info['per_device_label_bytes'] / 1e6:.2f}MB + CSR "
              f"{info['per_device_csr_bytes'] / 1e6:.2f}MB) = "
              f"{info['per_device_frac']:.2f}x of the replicated "
              f"{info['replicated_bytes'] / 1e6:.2f}MB")
    else:
        idx = QbSIndex.build(g, n_landmarks=args.landmarks, chunk=args.chunk)
        t1 = time.perf_counter()
        sz = labelling_size_bytes(idx.scheme)
        psz = packed_size_bytes(idx.packed)
        print(f"[serve] labelling built in {t1 - t0:.2f}s; "
              f"size(L)={sz['label_bytes'] / 1e6:.2f}MB "
              f"meta_edges={sz['n_meta_edges']}")
        print(f"[serve] packed tables: {psz['packed_bytes'] / 1e6:.2f}MB "
              f"({psz['dtype']}, {psz['ratio']:.1f}x smaller than int32)")

    rng = np.random.default_rng(args.seed)
    us = rng.integers(0, g.n_vertices, size=args.queries)
    vs = rng.integers(0, g.n_vertices, size=args.queries)

    n_replicas = args.replicas
    if args.metrics_port is not None and n_replicas == 0:
        n_replicas = 1
    router = server = None
    if n_replicas:
        from ..serving import MetricsRegistry, ReplicaRouter, serve_metrics
        router = ReplicaRouter(idx, n_replicas=n_replicas, cache_size=4096,
                               cache_policy="hub")
        print(f"[serve] replica tier: {n_replicas} replicas behind "
              f"consistent hashing")
        if args.metrics_port is not None:
            registry = MetricsRegistry()
            for i, rep in enumerate(router.replicas):
                registry.register(f"replica{i}", rep)
            server = serve_metrics(registry, port=args.metrics_port)
            print(f"[serve] metrics: http://127.0.0.1:"
                  f"{server.server_address[1]}/metrics")

    t2 = time.perf_counter()
    results = (router.query_batch(us, vs) if router is not None
               else idx.query_batch(us, vs))
    t3 = time.perf_counter()
    dists = np.array([r.dist for r in results], dtype=np.int64)
    sizes = np.array([r.edge_ids.size for r in results])
    print(f"[serve] {args.queries} queries in {t3 - t2:.2f}s "
          f"({(t3 - t2) / args.queries * 1e3:.2f} ms/query incl. host assembly)")
    finite = dists < (1 << 20)
    if finite.any():
        print(f"[serve] dist: mean={dists[finite].mean():.2f} "
              f"max={dists[finite].max()}; SPG edges: mean={sizes.mean():.1f} "
              f"max={sizes.max()}")

    if router is not None:
        routed = router.stats["routed"]
        per_rep = {i: rep.stats["submitted"]
                   for i, rep in enumerate(router.replicas)}
        print(f"[serve] router: {routed} routed, per-replica {per_rep}")
        if server is not None:
            server.shutdown()
        router.close()


if __name__ == "__main__":
    main()
