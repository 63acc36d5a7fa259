"""Pair kind ``uniform``: two distinct vertices, each uniformly at random
(the QbS paper's query workload, section 6).  No parameters."""
import numpy as np

# every lane a uniform pair can land in (u == v never is drawn)
LANES = ("general", "one_sided", "landmark_pair")


def draw(spec: dict, n_vertices: int, top: np.ndarray, n: int,
         rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    us = rng.integers(0, n_vertices, n)
    vs = rng.integers(0, n_vertices - 1, n)
    vs = vs + (vs >= us)               # distinct, still uniform
    return us.astype(np.int32), vs.astype(np.int32)
