"""Smoke run of the device engine (``backend="soa-device"``) on one TPU.

    python chip_smoke.py

Streams the paper's §5 synthetic set (``blobs``: 200,000 points, d=10,
10 clusters; k=10, t=10, eps=0.75, batches of 1,000) through
``build_index``:

  * kernel phase: hash the whole stream with ``ops.lsh_hash`` and check
    the keys bit for bit against the host mirror
    ``GridLSH.device_keys_batch``, which the shard router and bridge use;
  * stream phase: insert every point, expire the oldest 50,000 with
    ``delete_batch``, then answer 1,000 ``label()`` queries and one
    ``labels()``.  The host engine (``backend="soa"``) runs the same
    stream; labels, point labels and drained deltas must be equal at the
    end of the inserts and at the end of the deletes.

Earlier lines give each phase's wall time, the compiles after the first
insert batch, the largest slot capacity reached, the ARI against the
blobs' ground truth and the device kind.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  Any mismatch or exception
exits non-zero, and so does a run where JAX's first device is not a TPU,
printing no result.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCounter:
    """``jax.monitoring`` listener counting backend compiles (a persistent
    cache hit counts too: it stands in for one)."""

    def __init__(self) -> None:
        self.n = 0

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def _same_state(dev, host, phase: str) -> None:
    dd, hd = dev.drain_deltas(), host.drain_deltas()
    _require(dd == hd, f"{phase}: drained deltas differ "
             f"({len(dd)} soa-device vs {len(hd)} soa)")
    _require(dev.labels() == host.labels(), f"{phase}: labels() differ")


def run_phases(n: int = 200_000, n_expire: int = 50_000,
               n_queries: int = 1_000, seed: int = 0, log=print) -> dict:
    """Run both phases on ``n`` blobs points; raises on any mismatch.

    Returns the counts the log lines report, so a test can drive the
    phases at a small size on any platform.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ClusterConfig, build_index
    from repro.configs.paper_dbscan import CONFIG
    from repro.core import adjusted_rand_index
    from repro.data import DATASET_SPECS, blobs
    from repro.kernels import ops

    _, d, n_clusters = DATASET_SPECS["blobs"]
    X, truth = blobs(n=n, d=d, n_clusters=n_clusters, seed=seed)
    cfg = ClusterConfig(d=d, k=CONFIG.k, t=CONFIG.t, eps=CONFIG.eps,
                        seed=seed)
    batch = CONFIG.batch_size
    dev = build_index(cfg.replace(backend="soa-device"))
    host = build_index(cfg.replace(backend="soa"))
    out: dict = {"n": n, "n_expire": n_expire, "seconds": {}}
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        # -- kernel phase: device keys == host mirror, bit for bit
        lsh = dev.engine.lsh
        X32 = X.astype(np.float32)
        t0 = time.perf_counter()
        keys = np.asarray(ops.lsh_hash(
            jnp.asarray(X32), jnp.asarray(lsh.eta.astype(np.float32)),
            jnp.asarray(lsh.mixers), inv_cell=lsh.inv_cell))
        out["seconds"]["kernel_hash"] = time.perf_counter() - t0
        mirror = lsh.device_keys_batch(X32)
        bad = int((keys != mirror).any(axis=(1, 2)).sum())
        _require(bad == 0, f"lsh_hash: {bad} of {n} points' keys differ "
                 "from the host mirror")
        log(f"kernel: lsh_hash of {n}x{d} in "
            f"{out['seconds']['kernel_hash']:.3f} s (compile included); "
            "keys bit-equal to GridLSH.device_keys_batch")

        # -- stream phase: inserts
        for ix in (dev, host):
            ix.drain_deltas()  # the first drain starts the change feed
        ids = []
        t0 = time.perf_counter()
        ids += dev.insert_batch(X[:batch])
        out["compiles_first_batch"] = counter.n
        for s in range(batch, n, batch):
            ids += dev.insert_batch(X[s:s + batch])
        out["seconds"]["insert_soa_device"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        host_ids = []
        for s in range(0, n, batch):
            host_ids += host.insert_batch(X[s:s + batch])
        out["seconds"]["insert_soa"] = time.perf_counter() - t0
        _require(ids == host_ids, "insert: ids differ")
        _same_state(dev, host, "insert")
        log(f"insert: {n} points in batches of {batch}: soa-device "
            f"{out['seconds']['insert_soa_device']:.3f} s, soa "
            f"{out['seconds']['insert_soa']:.3f} s; labels and deltas equal")

        # -- stream phase: expire the oldest n_expire points
        for name, ix in (("soa_device", dev), ("soa", host)):
            t0 = time.perf_counter()
            for s in range(0, n_expire, batch):
                ix.delete_batch(ids[s:min(s + batch, n_expire)])
            out["seconds"][f"delete_{name}"] = time.perf_counter() - t0
        _same_state(dev, host, "delete")
        log(f"delete: oldest {n_expire} in batches of {batch}: soa-device "
            f"{out['seconds']['delete_soa_device']:.3f} s, soa "
            f"{out['seconds']['delete_soa']:.3f} s; labels and deltas equal")

        # -- queries: point labels, then one bulk labels()
        live = ids[n_expire:]
        rng = np.random.default_rng(seed)
        q = [live[i] for i in rng.choice(len(live), n_queries,
                                         replace=False)]
        t0 = time.perf_counter()
        got = [dev.label(i) for i in q]
        out["seconds"]["label_queries"] = time.perf_counter() - t0
        _require(got == [host.label(i) for i in q], "label(): answers differ")
        t0 = time.perf_counter()
        labels = dev.labels()
        out["seconds"]["labels"] = time.perf_counter() - t0
        pred = np.array([labels[i] for i in live])
        out["ari"] = adjusted_rand_index(truth[n_expire:], pred)
        log(f"query: {n_queries} label() in "
            f"{out['seconds']['label_queries']:.3f} s, one labels() in "
            f"{out['seconds']['labels']:.3f} s; point labels equal; "
            f"ARI vs blob truth {out['ari']:.6f}")
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)

    # one hash program per padded batch shape and two stats programs per
    # slot capacity, which doubles: O(log n), not one per batch
    out["compiles_after_first_batch"] = counter.n - out["compiles_first_batch"]
    out["slot_capacity"] = len(dev.engine._bsize)
    out["slots_used"] = dev.engine._n_slots
    bound = 2 * math.ceil(math.log2(n * cfg.t))
    _require(out["compiles_after_first_batch"] <= bound,
             f"{out['compiles_after_first_batch']} compiles after the first "
             f"batch, more than 2*log2(n*t) = {bound}")
    log(f"compiles: {out['compiles_first_batch']} up to the end of the "
        f"first insert batch (kernel phase included), "
        f"{out['compiles_after_first_batch']} after it; slot capacity "
        f"{out['slot_capacity']} ({out['slots_used']} slot ids allocated)")
    return out


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    run_phases()
    print(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
