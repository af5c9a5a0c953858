"""Plain reference for the index's semantics, and the comparison that
decides a run's ``correct``.

The reference imports nothing of the program.  From the live points alone
it recomputes what the configuration guarantees:

  * the grid-LSH keys (Definition 3 with the mixed-key family: one offset
    ``eta_i ~ U[0, 2 eps)`` per table, codes ``floor((x + eta_i) / 2eps)``
    in float32, two int32 universal mixes and a murmur3 finaliser), with
    the family drawn from the run's seed by the published recipe;
  * support: the number of tables in which a point's bucket holds at
    least ``k`` points (Definition 4); a point is core when it is >= 1;
  * core components: cores that share a bucket in any table are
    connected (Thm 2), components by ``scipy.sparse.csgraph``;
  * for every point and table, the component reachable through that
    bucket, so a border's label can be judged by what it says: it must be
    the cluster of a core sharing one of its buckets, and a point with no
    such core is noise.

Which core a border anchors to depends on the order of past events, and
any core sharing a bucket is a valid anchor, so the reference checks the
anchor's validity and not its identity.  Everything else is compared
exactly; each count below has the limit 0.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

NOISE = -1
_MIX_A = np.int32(-1975444243)  # murmur3 fmix32 constants as int32
_MIX_B = np.int32(-1029739211)


class LSHFamily:
    """The grid-LSH family for ``(d, eps, t, seed)``: per-table offsets
    and two families of odd int32 multipliers, drawn in this order from
    ``default_rng(seed)``."""

    def __init__(self, d: int, eps: float, t: int, seed: int):
        rng = np.random.default_rng(seed)
        self.t = t
        self.eta = rng.uniform(0.0, 2.0 * eps, size=t)
        self.inv_cell = 1.0 / (2.0 * eps)
        self.mixers = (rng.integers(1, 2**31 - 1, size=(2, t, d),
                                    dtype=np.int64).astype(np.int32)
                       | np.int32(1))


def _lsr(v: np.ndarray, s: int) -> np.ndarray:
    return (v.view(np.uint32) >> np.uint32(s)).view(np.int32)


def _fmix(h: np.ndarray) -> np.ndarray:
    h = h ^ _lsr(h, 16)
    h = h * _MIX_A
    h = h ^ _lsr(h, 13)
    h = h * _MIX_B
    return h ^ _lsr(h, 16)


def hash_keys(X: np.ndarray, fam: LSHFamily, dtype=np.float32,
              block: int = 4096) -> np.ndarray:
    """(n, d) points -> (n, t, 2) int32 keys, the codes computed in
    ``dtype`` (float32 is what the configuration states)."""
    n = len(X)
    out = np.empty((n, fam.t, 2), np.int32)
    eta = fam.eta.astype(dtype)[None, :, None]
    inv = np.asarray(fam.inv_cell, dtype)
    with np.errstate(over="ignore"):
        for s in range(0, n, block):
            x = X[s:s + block].astype(dtype)[:, None, :]
            codes = np.floor((x + eta) * inv).astype(np.int32)  # (b, t, d)
            for w in range(2):
                acc = (codes * fam.mixers[w][None]).sum(-1, dtype=np.int32)
                out[s:s + block, :, w] = _fmix(acc)
    return out


@dataclasses.dataclass
class Clustering:
    keys: np.ndarray      # (n, t, 2) int32
    bucket: np.ndarray    # (n, t) global bucket number
    support: np.ndarray   # (n,) int
    core: np.ndarray      # (n,) bool
    comp: np.ndarray      # (n,) component of a core, -1 otherwise
    reach: np.ndarray     # (n, t) component of the cores in that bucket, -1
    min_core: np.ndarray  # (n_buckets,) row of the least-id core, -1


def cluster(keys: np.ndarray, k: int,
            ids: Optional[np.ndarray] = None) -> Clustering:
    """Support, cores, core components and per-bucket reach of the points
    whose keys are ``keys``; ``ids`` orders rows for ``min_core``."""
    n, t = keys.shape[:2]
    key64 = ((keys[..., 0].astype(np.int64) << 32)
             | (keys[..., 1].astype(np.int64) & 0xFFFFFFFF))
    bucket = np.empty((n, t), np.int64)
    support = np.zeros(n, np.int64)
    offset = 0
    for i in range(t):
        uniq, inv, cnt = np.unique(key64[:, i], return_inverse=True,
                                   return_counts=True)
        support += cnt[inv] >= k
        bucket[:, i] = inv + offset
        offset += len(uniq)
    core = support > 0
    core_rows = np.nonzero(core)[0]
    m = len(core_rows)
    cb = bucket[core_rows].ravel()
    node = np.repeat(np.arange(m), t)
    order = np.argsort(cb, kind="stable")
    sb, sn = cb[order], node[order]
    same = sb[1:] == sb[:-1]
    a, b = sn[:-1][same], sn[1:][same]
    graph = coo_matrix((np.ones(len(a), np.int8), (a, b)), shape=(m, m))
    _, lab = connected_components(graph, directed=False)
    comp = np.full(n, -1, np.int64)
    comp[core_rows] = lab
    bucket_comp = np.full(offset, -1, np.int64)
    bucket_comp[cb] = np.repeat(lab, t)
    reach = bucket_comp[bucket]
    # least-id core per bucket (the control's anchor rule)
    order_ids = np.arange(n) if ids is None else np.asarray(ids)
    min_core = np.full(offset, -1, np.int64)
    by_id = core_rows[np.argsort(order_ids[core_rows], kind="stable")]
    rb = bucket[by_id]
    for i in range(t):  # reverse so the least id is written last
        min_core[rb[::-1, i]] = by_id[::-1]
    return Clustering(keys, bucket, support, core, comp, reach, min_core)


# -------------------------------------------------------------------- #
# what a run hands the comparison
# -------------------------------------------------------------------- #
@dataclasses.dataclass
class Outputs:
    """What the timed path produced, gathered once the window closed."""
    state: Dict[str, np.ndarray]   # snapshot: ids, points, keys, support,
    #                                attach (id-sorted rows)
    labels: Dict[int, int]         # labels() of every live point
    feed: List[list]               # every drained delta list, in order
    answers: List[Tuple[int, int, int]]  # (state no, position, handle)
    failed: int = 0                # calls that raised


@dataclasses.dataclass
class Expected:
    """What the caller knows: acknowledged ids by stream position, the
    live positions at the end, and the live range of each state."""
    ids_by_pos: np.ndarray   # program id acknowledged for each position
    lo: int
    hi: int
    states: Dict[int, Tuple[int, int]]  # state no -> (lo, hi)


def _remap(ids_sorted: np.ndarray, want: np.ndarray):
    """Rows of ``want`` in an id-sorted array, and a mask of those found."""
    if len(ids_sorted) == 0:
        return np.zeros(len(want), np.int64), np.zeros(len(want), bool)
    pos = np.clip(np.searchsorted(ids_sorted, want), 0, len(ids_sorted) - 1)
    return pos, ids_sorted[pos] == want


def _anchor_ok(ref: Clustering, row: np.ndarray, arow: np.ndarray) -> np.ndarray:
    """Is the point at ``arow`` a core sharing a bucket with ``row``?"""
    return ref.core[arow] & (ref.bucket[row] == ref.bucket[arow]).any(1)


def compare(points: np.ndarray, ref: Clustering, exp: Expected,
            out: Outputs, state_points=None, k: int = 0,
            fam: Optional[LSHFamily] = None) -> Dict[str, int]:
    """Count every departure of ``out`` from the reference.

    ``points`` are the live points at the end, in position order, and
    ``ref`` their clustering.  ``state_points(lo, hi)`` gives the points of
    an earlier state, so that ``label()`` answers given in the window are
    judged against the state they were given in.
    """
    n = exp.hi - exp.lo
    want = np.asarray(exp.ids_by_pos[exp.lo:exp.hi], np.int64)
    st = out.state
    sids = np.asarray(st["ids"], np.int64)
    row, found = _remap(sids, want)
    counts: Dict[str, int] = {"failed_calls": int(out.failed)}

    # every acknowledged live insert is there with its coordinates, and
    # nothing else is (expired points are gone)
    extra = len(sids) - int(found.sum())
    same_pts = np.zeros(n, bool)
    same_pts[found] = (np.asarray(st["points"])[row[found]]
                       == points[found]).all(1)
    counts["live_mismatch"] = int(extra + (~same_pts).sum())

    t = ref.keys.shape[1]
    keys = np.ascontiguousarray(st["keys"], np.uint8).reshape(
        len(sids), t * 8).view(np.int32).reshape(len(sids), t, 2)
    key_ok = np.zeros(n, bool)
    key_ok[found] = (keys[row[found]] == ref.keys[found]).all((1, 2))
    counts["key_mismatch"] = int((~key_ok).sum())

    supp_ok = np.zeros(n, bool)
    supp_ok[found] = np.asarray(st["support"])[row[found]] == ref.support[found]
    counts["support_mismatch"] = int((~supp_ok).sum())

    # labels(): cores carry one label per reference component and no two
    # components share one; a border carries the label of a component it
    # reaches; a point that reaches none is noise
    lab = np.array([out.labels.get(int(i), -2) for i in want], np.int64)
    bad = lab == -2
    core = ref.core
    cl, cc = lab[core], ref.comp[core]
    ncomp = int(ref.comp.max()) + 1 if core.any() else 0
    comp_label = np.full(ncomp, -2, np.int64)
    comp_label[cc[::-1]] = cl[::-1]  # first label seen per component
    bad[core] |= (cl != comp_label[cc]) | (cl == NOISE)
    seen = comp_label[comp_label >= 0]
    dup = len(seen) - len(np.unique(seen))  # components sharing a label
    reach_lab = np.where(ref.reach >= 0, comp_label[np.maximum(ref.reach, 0)],
                         -3)
    border = ~core & (ref.reach >= 0).any(1)
    bad[border] |= ~(reach_lab[border] == lab[border, None]).any(1)
    noise = ~core & ~border
    bad[noise] |= lab[noise] != NOISE
    counts["label_mismatch"] = int(bad.sum() + dup)

    # anchors: a core anchors to itself; a border to a core sharing one of
    # its buckets; noise to nothing
    attach = np.full(n, -2, np.int64)
    attach[found] = np.asarray(st["attach"])[row[found]]
    pos_of = _position_index(exp)
    arow = np.array([pos_of.get(int(a), -1) for a in attach], np.int64)
    arow = np.where((arow >= exp.lo) & (arow < exp.hi), arow - exp.lo, -1)
    idx = np.arange(n)
    good = np.zeros(n, bool)
    good[core] = attach[core] == -1
    bi = idx[border]
    ok_b = (arow[bi] >= 0)
    ok_b[ok_b] = _anchor_ok(ref, bi[ok_b], arow[bi][ok_b])
    good[bi] = ok_b
    good[noise] = attach[noise] == -1
    counts["anchor_mismatch"] = int((~good).sum())
    expect_anchor = np.where(core, want, np.where(border, attach, -1))

    # the change feed: each delta's old handle is what the feed said last,
    # and replaying every delta gives each live point's anchor and leaves
    # every expired point with none
    cur: Dict[int, Optional[int]] = {}
    chain = 0
    for deltas in out.feed:
        for idx_, old, new in deltas or ():
            if cur.get(idx_) != old:
                chain += 1
            if new is None:
                cur.pop(idx_, None)
            else:
                cur[idx_] = new
    got = np.array([-1 if cur.get(int(i)) is None else cur[int(i)]
                    for i in want], np.int64)
    live = set(want.tolist())
    stale = sum(1 for i in cur if i not in live)
    counts["feed_mismatch"] = int(chain + stale + (got != expect_anchor).sum())

    if out.answers:
        counts["answer_mismatch"] = _judge_answers(
            exp, out.answers, state_points, k, fam, pos_of)
    return counts


def _position_index(exp: Expected) -> Dict[int, int]:
    return {int(i): p for p, i in enumerate(exp.ids_by_pos)}


def _judge_answers(exp: Expected, answers, state_points, k: int,
                   fam: LSHFamily, pos_of: Dict[int, int]) -> int:
    """``label(id)`` answers a component handle: the id of the
    component's representative core for a core or an attached border, the
    point's own id for noise.  Judge each answer against the state it was
    given in: one handle per component, every handle a core of a
    component the point reaches."""
    by_state: Dict[int, list] = {}
    for s, p, h in answers:
        by_state.setdefault(s, []).append((p, h))
    bad = 0
    for s, lst in sorted(by_state.items()):
        lo, hi = exp.states[s]
        ids = np.asarray(exp.ids_by_pos[lo:hi], np.int64)
        ref = cluster(hash_keys(state_points(lo, hi), fam), k, ids)
        handle_of: Dict[int, int] = {}
        for p, h in lst:
            if not isinstance(h, (int, np.integer)):
                bad += 1
                continue
            r = p - lo
            hp = pos_of.get(int(h), -1)
            hr = hp - lo if lo <= hp < hi else -1
            if ref.core[r]:
                ok = hr >= 0 and ref.core[hr] and ref.comp[hr] == ref.comp[r]
                c = int(ref.comp[r])
            elif (ref.reach[r] >= 0).any():
                ok = hr >= 0 and ref.core[hr] and ref.comp[hr] in ref.reach[r]
                c = int(ref.comp[hr]) if ok else -1
            else:
                ok, c = int(h) == int(exp.ids_by_pos[p]), -1
            if ok and c >= 0:
                ok = handle_of.setdefault(c, int(h)) == int(h)
            bad += not ok
    return bad


# -------------------------------------------------------------------- #
# the control: the reference in the program's place, in lower precision
# -------------------------------------------------------------------- #
def reference_outputs(points: np.ndarray, ids: np.ndarray, fam: LSHFamily,
                      k: int, hash_dtype=np.float32,
                      coord_dtype=np.float64, lo: int = 0,
                      queries: Sequence[int] = ()) -> Outputs:
    """What the reference would answer in the program's place for the live
    ``points`` (ids ``ids``): a snapshot, ``labels()``, a change feed from
    an empty index, and ``label()`` answers for the stream positions
    ``queries`` (``points`` start at position ``lo``; one state, no. 0).
    Borders anchor to the least-id core of their first table that has
    one.  With the configuration's precisions it must compare as correct;
    computed one step lower it is the control, which must not."""
    pts = points.astype(coord_dtype).astype(np.float64)
    ref = cluster(hash_keys(pts, fam, hash_dtype), k, ids)
    n, t = len(ids), fam.t
    anchor = np.full(n, -1, np.int64)
    cand = np.where(ref.reach >= 0, ref.min_core[ref.bucket], -1)
    has = cand >= 0
    first = np.where(has.any(1), cand[np.arange(n), has.argmax(1)], -1)
    border = ~ref.core & (first >= 0)
    anchor[border] = ids[first[border]]
    comp_rep = np.full(int(ref.comp.max()) + 2, -1, np.int64)
    core_rows = np.nonzero(ref.core)[0]
    by_id = core_rows[np.argsort(ids[core_rows], kind="stable")]
    comp_rep[ref.comp[by_id[::-1]]] = ids[by_id[::-1]]
    comp_of = np.where(ref.core, ref.comp,
                       np.where(border, ref.comp[np.maximum(first, 0)], -1))
    labels = {int(i): (int(c) if c >= 0 else NOISE)
              for i, c in zip(ids, comp_of)}
    order = np.argsort(ids)
    state = {
        "ids": ids[order],
        "points": pts[order],
        "keys": ref.keys[order].view(np.uint8).reshape(n, t, 8),
        "support": ref.support[order],
        "attach": anchor[order],
    }
    feed = [[(int(i), None, int(i) if ref.core[r] else int(anchor[r]))
             for r, i in enumerate(ids) if ref.core[r] or anchor[r] >= 0]]
    answers = [(0, int(q), int(comp_rep[comp_of[q - lo]])
                if comp_of[q - lo] >= 0 else int(ids[q - lo]))
               for q in queries]
    return Outputs(state=state, labels=labels, feed=feed, answers=answers)
