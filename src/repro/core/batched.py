"""Beyond-paper batched update path: device hashing + host structure.

The paper processes a batch of B updates as B sequential O(polylog)
operations, each paying O(t·d) hashing on the host.  On TPU the hashing is
one ``lsh_hash`` kernel call over the whole batch (bandwidth-bound, ~t
ops/byte); only the (B, t, 2) int32 keys come back to the host, which then
performs the pointer updates.  The clustering is identical (H is invariant
to update order and to the key representation — §4.2), the throughput is
not: see benchmarks/kernels.py.

``BatchedDynamicDBSCAN`` shares all the machinery of ``DynamicDBSCAN`` but
keys every bucket by the kernel's mixed keys, so single-point and batch
updates interoperate.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .dynamic_dbscan import DynamicDBSCAN, check_unique_ids, claim_index
from .hashing import GridLSH


class BatchedDynamicDBSCAN(DynamicDBSCAN):
    def __init__(self, d, k, t, eps, seed: int = 0, use_device: bool = False,
                 attach_orphans: bool = True, lsh: Optional[GridLSH] = None,
                 repair: str = "exact"):
        super().__init__(d, k, t, eps, seed=seed,
                         attach_orphans=attach_orphans, lsh=lsh, repair=repair)
        self.use_device = use_device

    # key space: kernel mixed keys (int32 pairs) instead of exact codes
    def _keys_of_batch(self, X: np.ndarray) -> List[list]:
        X = np.asarray(X, dtype=np.float32)
        if self.use_device:
            keys = np.asarray(self._device_hash(X))
        else:
            keys = self.lsh.device_keys_batch(X)
        return [
            [keys[j, i].tobytes() for i in range(self.t)]
            for j in range(X.shape[0])
        ]

    def _device_hash(self, X: np.ndarray):
        import jax.numpy as jnp

        from repro.kernels import ops

        return ops.lsh_hash(
            jnp.asarray(X),
            jnp.asarray(self.lsh.eta.astype(np.float32)),
            jnp.asarray(self.lsh.mixers),
            inv_cell=self.lsh.inv_cell,
        )

    def add_point(self, x: np.ndarray, idx: Optional[int] = None) -> int:
        return self.add_batch(
            np.asarray(x, dtype=np.float64)[None], ids=[idx]
        )[0]

    def add_batch(self, X: np.ndarray,
                  ids: Optional[Sequence[Optional[int]]] = None) -> List[int]:
        """Hash the whole batch in one kernel call, then apply updates.

        ``ids`` optionally pins explicit indices (None entries auto-assign),
        mirroring the parent class's ``add_point(x, idx)`` contract.
        """
        X = np.asarray(X, dtype=np.float64)
        if X.ndim != 2 or X.shape[1] != self.d:
            raise ValueError(f"batch shape {X.shape} != (n, {self.d})")
        if ids is not None and len(ids) != X.shape[0]:
            raise ValueError("ids length must match batch size")
        keys = self._keys_of_batch(X)
        out = []
        for j in range(X.shape[0]):
            idx, self._next_idx = claim_index(
                self.points, self._next_idx,
                ids[j] if ids is not None else None,
            )
            out.append(self._add_with_keys(X[j], keys[j], idx))
        # batch boundary: squash the change feed (drain_deltas) so a
        # B-point run contributes O(touched ids), not O(B·t), entries
        self._compact_journal()
        return out

    def delete_batch(self, ids: Sequence[int]) -> None:
        check_unique_ids(ids)
        for i in ids:
            self.delete_point(i)
        self._compact_journal()
