"""The QbS dry-run cells (``repro.launch.dryrun``) lower and compile
their programs from shapes alone, here at a small ``GraphScale`` on a
one-device mesh, and report the compiled program's memory."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.configs.qbs_graphs import GraphScale
from repro.launch import dryrun

CELLS = [
    ("labelling-bool", dryrun.lower_qbs_labelling_cell, {"frontier_mode": "bool"}),
    ("labelling-bitmap", dryrun.lower_qbs_labelling_cell, {"frontier_mode": "bitmap"}),
    ("labelling-pull", dryrun.lower_qbs_labelling_cell, {"frontier_mode": "pull"}),
    ("serve", dryrun.lower_qbs_serve_cell, {}),
    ("sharded-serve", dryrun.lower_qbs_sharded_serve_cell, {"batch": 4}),
]


@pytest.mark.parametrize("cell,lower,kw", CELLS, ids=[c[0] for c in CELLS])
def test_cell_reports_memory_without_allocating(monkeypatch, cell, lower, kw):
    monkeypatch.setitem(dryrun.GRAPHS, "tiny", GraphScale("tiny", 300, 900, 4))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    before = {id(a) for a in jax.live_arrays()}
    stats = lower("tiny", mesh, **kw)
    assert {id(a) for a in jax.live_arrays()} <= before
    assert stats["graph"] == {"V": 300, "E_directed": 1800, "R": 4}
    assert stats["n_devices"] == 1
    mem = stats["memory"]
    assert "error" not in mem, mem
    assert mem["argument_bytes"] > 0 and mem["output_bytes"] > 0
    assert stats["flops"] >= 0 and stats["n_hlo_lines"] > 0
