"""The comparison that decides ``correct``: the reference in the program's
place compares clean at the configuration's precisions and fails one
step below them (the control), and a run whose timed path is broken
underneath comes out not correct, once for each fault a cell can have."""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import harness  # noqa: E402

from repro.api import build_index  # noqa: E402

WORKLOADS = ["blobs-d10.ingest", "blobs-d10.query"]
SEEDS = [3, 2**31 + 5, 4_000_000_007]


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_and_stated_precision_passes(workload, seed):
    same = control.readings(workload, seed, steps=2, lower=False,
                            live_window=3000)
    assert not any(same.values()), same
    low = control.readings(workload, seed, steps=2, lower=True,
                           live_window=3000)
    assert any(v > 0 for v in low.values()), low
    assert low["key_mismatch"] > 0 and low["live_mismatch"] > 0


class _Faulty:
    """The index with one fault planted under the harness."""

    def __init__(self, index, fault: str):
        self._ix, self._fault = index, fault
        self._fake = 10**9
        if fault == "key_altered":
            eng = index.engine
            hash_batch = eng._hash_batch

            def altered(X):
                keys = np.array(hash_batch(X))
                keys[0, 0, 0] ^= 1  # one key, where it is produced
                return keys

            eng._hash_batch = altered

    def __getattr__(self, name):
        return getattr(self._ix, name)

    def insert_batch(self, X):
        if self._fault == "unchanged_insert":
            ids = list(range(self._fake, self._fake + len(X)))
            self._fake += len(X)
            return ids  # acknowledged, state unchanged
        if self._fault == "half_batch":
            ids = self._ix.insert_batch(X[:len(X) // 2])
            fake = list(range(self._fake, self._fake + len(X) - len(ids)))
            self._fake += len(fake)
            return ids + fake
        return self._ix.insert_batch(X)

    def delete_batch(self, ids):
        if self._fault == "unchanged_expire":
            return None  # acknowledged, state unchanged
        return self._ix.delete_batch(ids)

    def label(self, idx):
        h = self._ix.label(idx)
        return h + 1 if self._fault == "answer_altered" else h

    def labels(self, ids=None):
        out = self._ix.labels(ids)
        if self._fault == "answer_altered":
            first = next(iter(out))
            out[first] = max(out.values()) + 1
        return out


FAULTS = ["unchanged_insert", "unchanged_expire", "half_batch",
          "key_altered", "answer_altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_broken_timed_path_is_not_correct(workload, fault):
    r = harness.run_cell(
        workload, 11, 0.3, False, overrides={"live_window": 2000},
        make_index=lambda cfg: _Faulty(build_index(cfg), fault),
        log=lambda s: None)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
