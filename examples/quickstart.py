"""Quickstart: dynamic DBSCAN through the unified repro.api in a dozen lines.

    PYTHONPATH=src python examples/quickstart.py [--backend dynamic]
"""
import argparse

import numpy as np

from repro.api import ClusterConfig, available_backends, build_index
from repro.compile_cache import enable_compile_cache
from repro.core import adjusted_rand_index
from repro.data import blobs

ap = argparse.ArgumentParser()
ap.add_argument("--backend", default="dynamic", choices=available_backends())
args = ap.parse_args()
enable_compile_cache()

# 2000 points from 5 Gaussian blobs, streamed one at a time
X, y = blobs(n=2000, d=5, n_clusters=5, cluster_std=0.15, seed=0)

db = build_index(ClusterConfig(d=5, k=10, t=10, eps=0.4, seed=0,
                               backend=args.backend))
ids = db.insert_batch(X)

# clusters update dynamically: delete the first 500 points again
db.delete_batch(ids[:500])

labels = db.labels()                     # bulk labels (noise = -1)
cluster_of_point_700 = db.label(ids[700])   # O(log n) point query

pred = np.array([labels[i] for i in ids[500:]])
print("backend:", args.backend)
print("ARI vs ground truth:", round(adjusted_rand_index(y[500:], pred), 4))
print("clusters:", len({v for v in pred if v != -1}),
      " noise points:", int((pred == -1).sum()))
