"""HipMCL end-to-end: protein-clustering pipeline (paper §7.5).

Generates a synthetic protein-similarity network with planted clusters,
writes it in the MCL LABEL format (string protein ids), reads it back with
the two-pass ReadGeneralizedTuples reader (which relabels + load-balances),
clusters with Markov clustering, and reports cluster quality.

    PYTHONPATH=src python examples/hipmcl_clustering.py
"""
import os
import tempfile

import numpy as np

from repro.apps import hipmcl
from repro.core import DistSpMat, make_grid
from repro.io import read_generalized_tuples
from repro.launch.cache import use_compile_cache


def planted_network(k=6, size=12, p_in=0.7, p_out=0.01, seed=0):
    rng = np.random.default_rng(seed)
    n = k * size
    edges = []
    for i in range(n):
        for j in range(i + 1, n):
            same = i // size == j // size
            if rng.random() < (p_in if same else p_out):
                w = rng.random() * 0.5 + (0.5 if same else 0.05)
                edges.append((i, j, w))
    return n, edges


def main():
    use_compile_cache()
    n, edges = planted_network()
    truth = np.arange(n) // 12
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "proteins.lbl")
        with open(path, "w") as f:
            for i, j, w in edges:
                f.write(f"PROT_{i:04d}\tPROT_{j:04d}\t{w:.4f}\n")
                f.write(f"PROT_{j:04d}\tPROT_{i:04d}\t{w:.4f}\n")
        shape, rows, cols, vals, labels = read_generalized_tuples(path, 4)
        print(f"read {shape[0]} proteins, {len(rows)} similarities "
              f"(labels relabeled + load-balanced)")
        # self-loops (MCL standard)
        loops = np.arange(shape[0], dtype=np.int64)
        rows = np.concatenate([rows, loops])
        cols = np.concatenate([cols, loops])
        vals = np.concatenate([vals, np.full(shape[0], 1.0)])
        mesh = make_grid(1, 1)
        A = DistSpMat.from_global_coo(shape, rows, cols, vals, (1, 1),
                                      mesh=mesh)
        clusters = hipmcl(A, mesh=mesh, inflation=2.0, max_iters=10,
                          prod_cap=1 << 17, out_cap=1 << 15)
    # map back through the label permutation and score vs planted truth
    orig = np.array([int(lb.split("_")[1]) for lb in labels])
    pred = np.empty(n, np.int64)
    pred[orig] = clusters
    # purity
    correct = 0
    for c in set(pred):
        members = truth[pred == c]
        correct += np.bincount(members).max()
    print(f"clusters found: {len(set(pred))} (planted 6), "
          f"purity {correct / n:.3f}")


if __name__ == "__main__":
    main()
