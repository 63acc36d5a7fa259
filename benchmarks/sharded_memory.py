"""Per-device memory accounting of the vertex-sharded index (DESIGN.md
§11): build the born-sharded labels + CSR partition over an 8-way mesh
and record what each device actually holds vs the replicated layout.

The acceptance metric is ``per_device_frac`` = (per-device label + CSR
bytes) / (replicated label + CSR bytes); the bench gate holds it under
an absolute linear-scaling ceiling (``--shard-frac-ceiling``, default
0.25 on the 8-way mesh) rather than a relative threshold — the fraction
is a property of the partition, not of machine speed.

Self-spawning: ``run()`` re-execs this module in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the 8-way mesh
exists regardless of how many devices the invoking process sees — the
bench works from any CI step (or a dev laptop) without env gymnastics.
The child runs with ``JAX_PLATFORMS=cpu``: what it measures is a byte
count, and an accelerator belongs to the parent process that may already
hold it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
BENCH_PATH = REPO / "BENCH.json"
N_SHARDS = 8
_MARK = "SHARDED-MEMORY-JSON:"


def _child(scale: float) -> None:
    """Runs under the forced 8-device env: build and measure."""
    import jax

    from repro.core import barabasi_albert_graph, random_regular_graph
    from repro.core.sharded import ShardedIndex

    assert len(jax.devices()) >= N_SHARDS, jax.devices()
    n1 = max(512, int(4_000 * scale))
    n2 = max(512, int(3_000 * scale))
    out = []
    for gname, g in (("ba-hub", barabasi_albert_graph(n1, 3, seed=1)),
                     ("reg-flat", random_regular_graph(n2, 8, seed=3))):
        idx = ShardedIndex.build(g, n_landmarks=20, mesh=N_SHARDS)
        info = idx.sharded_size_bytes()
        out.append({
            "graph": gname, "n_shards": info["n_shards"],
            "V": g.n_vertices, "E": g.n_edges,
            "dtype": str(idx.labels.pack_dtype),
            "per_device_frac": float(info["per_device_frac"]),
            "per_device_bytes": float(info["per_device_bytes"]),
            "per_device_label_bytes": float(info["per_device_label_bytes"]),
            "per_device_csr_bytes": float(info["per_device_csr_bytes"]),
            "replicated_bytes": float(info["replicated_bytes"]),
        })
    print(_MARK + json.dumps(out))


def run(scale: float = 1.0, **_) -> list[tuple]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_SHARDS}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--child",
         str(scale)],
        env=env, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError("sharded_memory child failed:\n"
                           f"{proc.stdout}\n{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            payload = json.loads(line[len(_MARK):])
    assert payload is not None, proc.stdout
    record = {"bench": "sharded_memory", "ts": time.time(), "scale": scale,
              "rows": payload}
    with BENCH_PATH.open("a") as f:
        f.write(json.dumps(record) + "\n")
    return [(f"sharded_memory/{r['graph']}/S{r['n_shards']}",
             r["per_device_bytes"],
             f"frac={r['per_device_frac']:.3f},dtype={r['dtype']}")
            for r in payload]


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child(float(sys.argv[sys.argv.index("--child") + 1]))
    else:
        sys.path.insert(0, str(REPO))
        from benchmarks.common import emit

        print("name,per_device_bytes,derived")
        emit(run())
