"""The run's graph, made on the host from the configuration and the seed."""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import scipy.sparse as sps

from bench import cells
from bench.reference.graphs import adjacency

# room a tile keeps above its share of the stored entries
TILE_PAD = 1.25


def rng(seed: int, salt: int = 0) -> np.random.Generator:
    """A generator for ``--seed`` (any whole number), one stream per salt."""
    return np.random.default_rng([seed & (2**64 - 1), salt])


@dataclasses.dataclass
class Graph:
    n: int
    rows: np.ndarray        # entry (rows[i], cols[i]) in the run's labels
    cols: np.ndarray
    label: np.ndarray       # label[v]: the run's label of generator vertex v
    host: sps.csr_matrix    # float64 host copy, for work counts and checks
    tile_cap: int           # entries a tile holds on the configuration's grid

    def canonical(self, v):
        """Generator labels of run labels ``v``."""
        inv = np.empty_like(self.label)
        inv[self.label] = np.arange(self.n)
        return inv[v]


def tile_cap(entries: int, tiles: int, pad: float = TILE_PAD) -> int:
    """Capacity of one tile: its share of ``entries`` with ``pad`` room, a
    multiple of 8. It depends on the entry count alone, which the run's
    labels do not change, so every seed runs the same programs."""
    return max(8, math.ceil(entries * pad / tiles / 8) * 8)


def relabel(n: int, blocks: int, seed: int) -> np.ndarray:
    """A random permutation of the ``n`` vertices that keeps each of
    ``blocks`` equal ranges of them in place (one range: any permutation)."""
    r, size = rng(seed), n // blocks
    return np.concatenate([k * size + r.permutation(size)
                           for k in range(blocks)])


def build(config: dict, seed: int) -> Graph:
    """The configuration's graph, its vertices relabelled from ``seed``.

    The generator (``bench/gen/<generator>.py``) has its own seed, fixed by
    the configuration, and permutes the vertices as Graph500 does. ``seed``
    relabels them again inside each of the grid's row (= column) blocks, so
    every tile holds the same entries under other labels: every run holds
    the same graph, the planner sees the same tile counts and every seed
    does the same work."""
    pr, pc = config["grid"]
    n, r, c = cells.generator(config["generator"]).graph(config)
    if pr != pc or n % pr:
        raise ValueError(f"a {pr}x{pc} grid does not split {n} vertices "
                         "into square tiles")
    label = relabel(n, pr, seed)
    rows, cols = label[r], label[c]
    return Graph(n, rows, cols, label, adjacency(n, rows, cols),
                 tile_cap(len(rows), pr * pc))
