"""Roofline terms of the QbS dry-run cells (``repro.launch.dryrun``).

Per cell on the chosen mesh, from the compiled program's HLO summary:

    compute term    = HLO_FLOPs_per_device / 197e12      (bf16 peak, v5e)
    memory term     = HLO_bytes_per_device / 819e9       (HBM bw)
    collective term = collective_bytes_per_device / 50e9 (ICI link bw)

XLA's cost analysis visits a while-loop body once, so each term is one
BFS level's.

  PYTHONPATH=src python -m benchmarks.roofline [--mesh single] [--md out.md]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path


PEAK_FLOPS = 197e12       # bf16 / chip (TPU v5e)
HBM_BW = 819e9            # B/s / chip
LINK_BW = 50e9            # B/s / ICI link

RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def qbs_rows(mesh: str = "single") -> list[dict]:
    rows = []
    for p in sorted(RESULTS.glob(f"qbs-*__*__{mesh}.json")):
        data = json.loads(p.read_text())
        if "error" in data:
            continue
        coll = {k: v for k, v in data["collectives"].items() if k != "_counts"}
        cb = float(sum(coll.values()))
        terms = {
            "compute": data["flops"] / PEAK_FLOPS,
            "memory": data["bytes_accessed"] / HBM_BW,
            "collective": cb / LINK_BW,
        }
        dominant = max(terms, key=terms.get)  # type: ignore[arg-type]
        rows.append({
            "cell": p.stem,
            "t_compute_s": terms["compute"],
            "t_memory_s": terms["memory"],
            "t_collective_s": terms["collective"],
            "dominant": dominant,
            "collective_bytes": cb,
            "note": "per-BFS-level terms (while-loop body; multiply by "
                    "expected diameter ~8-12 levels, paper Fig. 7)",
        })
    return rows


def to_markdown(rows: list[dict], title: str) -> str:
    out = [f"### {title}", "",
           "| cell | compute (s) | memory (s) | collective (s) | dominant | note |",
           "|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['cell']} | {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
            f"| {r['t_collective_s']:.3e} | **{r['dominant']}** | {r['note']} |")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# Measured kernel roofline (bench_gate-gated): the packed hot-path kernels'
# XLA reference math timed against an optimistic CPU roofline.  Unlike the
# dry-run analysis above (modelled TPU terms from compiled artifacts), these
# rows are *measurements* on the machine running the bench: achieved
# fraction = ideal time at peak / measured wall time, clamped to 1.  The
# fractions land in BENCH.json and scripts/bench_gate.py holds them above an
# absolute floor (--frac-floor) — a kernel silently falling off its roofline
# (accidental dtype widening, a dense materialization of the packed words)
# shows up as a collapsed fraction long before qps notices.
# ---------------------------------------------------------------------------

CPU_PEAK_FLOPS = 5e10     # optimistic single-socket f32 peak (CI runners)
CPU_MEM_BW = 2e10         # B/s; together these overestimate, which is fine:
                          # the floor gates collapse, not absolute efficiency

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH.json"


def _ideal_us(flops: float, bytes_moved: float) -> float:
    return max(flops / CPU_PEAK_FLOPS, bytes_moved / CPU_MEM_BW) * 1e6


def run(scale: float = 1.0, **_) -> list[tuple]:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.graph import INF
    from repro.core.packing import pack_bits, pack_dist, unpack_bits
    from repro.core.sketch import d_top_only

    from .common import interleaved_best

    rng = np.random.default_rng(0)

    # -- hub-relay expand over bit-packed words (kernels/frontier.py) ------
    h = max(512, int(2048 * scale))
    r = 64
    f = jnp.asarray(rng.random((r, h)) < 0.05)
    words = pack_bits(jnp.asarray(rng.random((h, h)) < 0.02))

    @jax.jit
    def expand(f, words):
        a = unpack_bits(words, h).astype(jnp.float32)
        return jnp.dot(f.astype(jnp.float32), a,
                       preferred_element_type=jnp.float32) > 0.5

    expand_flops = 2.0 * r * h * h
    expand_bytes = float(f.nbytes + words.nbytes + r * h)

    # -- Eq. 3 min-plus sketch contraction over packed labels --------------
    b_q = max(128, int(512 * scale))
    n_lm = 64
    lu_i = rng.integers(0, 200, size=(b_q, n_lm)).astype(np.int32)
    lu_i[rng.random((b_q, n_lm)) < 0.3] = INF
    dm_i = rng.integers(0, 200, size=(n_lm, n_lm)).astype(np.int32)
    lu = pack_dist(lu_i, np.uint8)
    lv = pack_dist(lu_i[::-1].copy(), np.uint8)
    dm = pack_dist(dm_i, np.uint8)
    sketch = jax.jit(d_top_only)

    sketch_flops = 2.0 * 2 * b_q * n_lm * n_lm   # two (min, +) contractions
    sketch_bytes = float(lu.nbytes + lv.nbytes + dm.nbytes + 4 * b_q)

    cells = {
        "bitmap_expand": lambda: expand(f, words).block_until_ready(),
        "minplus_sketch": lambda: sketch(lu, lv, dm).block_until_ready(),
    }
    best = interleaved_best(cells, rounds=12)

    specs = {
        "bitmap_expand": (f"{r}x{h}", expand_flops, expand_bytes),
        "minplus_sketch": (f"{b_q}x{n_lm}", sketch_flops, sketch_bytes),
    }
    rows: list[tuple] = []
    record = {"bench": "roofline", "ts": time.time(), "scale": scale,
              "rows": []}
    for kernel, dt in best.items():
        shape, flops, nbytes = specs[kernel]
        wall_us = dt * 1e6
        ideal = _ideal_us(flops, nbytes)
        frac = min(ideal / max(wall_us, 1e-9), 1.0)
        rows.append((f"roofline/{kernel}/{shape}", wall_us,
                     f"frac={frac:.4f},ideal_us={ideal:.1f}"))
        record["rows"].append({"kernel": kernel, "shape": shape,
                               "roofline_frac": frac, "wall_us": wall_us,
                               "ideal_us": ideal})
    with BENCH_PATH.open("a") as fh:
        fh.write(json.dumps(record) + "\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--md", default=None)
    args = ap.parse_args()
    md = to_markdown(qbs_rows(args.mesh), f"QbS engine cells ({args.mesh}-pod)")
    if args.md:
        Path(args.md).write_text(md)
    print(md)


if __name__ == "__main__":
    main()
