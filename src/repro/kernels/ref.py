"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes and
dtypes and asserts allclose (bit-exact for the integer hash) against these.
They are also the *portable* implementations used when lowering for
backends where the Mosaic TPU kernels are unavailable (e.g. the CPU
dry-run).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# murmur3-style finalizer constants (int32 wrap-around arithmetic);
# plain Python ints so Pallas kernels don't capture traced constants
MIX_A = -1975444243  # 0x85EBCA6D as int32
MIX_B = -1029739211  # 0xC2B2AE35 as int32


def _avalanche(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * MIX_A
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * MIX_B
    h = h ^ jax.lax.shift_right_logical(h, 16)
    return h


def lsh_hash(x: jnp.ndarray, eta: jnp.ndarray, mixers: jnp.ndarray,
             inv_cell: float) -> jnp.ndarray:
    """Grid-LSH bucket keys.

    x:      (n, d) float32 points
    eta:    (t,)   float32 per-table offsets (the paper's eta * 1_d)
    mixers: (2, t, d) int32 odd multipliers (two independent families)
    returns (n, t, 2) int32 keys; two points share a bucket in table i iff
    their grid-code vectors match — keys collide spuriously w.p. ~2^-64.
    """
    codes = jnp.floor(
        (x[:, None, :] + eta[None, :, None]) * jnp.float32(inv_cell)
    ).astype(jnp.int32)  # (n, t, d)
    # (n, t, d) * (t, d) summed over d, int32 wrap-around
    acc_a = jnp.sum(codes * mixers[0][None], axis=-1, dtype=jnp.int32)
    acc_b = jnp.sum(codes * mixers[1][None], axis=-1, dtype=jnp.int32)
    return jnp.stack([_avalanche(acc_a), _avalanche(acc_b)], axis=-1)


def bucket_core_stats(slots: jnp.ndarray, sizes: jnp.ndarray, k: int):
    """Definition-4 support counts from bucket occupancies.

    slots: (n, t) int32 bucket-slot ids (host-resolved directory entries)
    sizes: (nb,) int32 current occupancy per slot
    returns (support, core): (n,) int32 ``#{i : sizes[slots[p,i]] >= k}``
    and the core indicator ``support > 0``.
    """
    occ = jnp.take(sizes, slots, axis=0)
    supp = jnp.sum((occ >= k).astype(jnp.int32), axis=-1)
    return supp, (supp > 0).astype(jnp.int32)


def slot_counts(slots: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """Occupancy histogram of a batch's (n, t) slot matrix:
    ``out[s] = #{(p, i) : slots[p, i] == s}`` — the bucket-size delta one
    insert batch contributes."""
    flat = slots.reshape(-1)
    return jnp.zeros((n_slots,), jnp.int32).at[flat].add(1, mode="drop")


def core_components(slots: jnp.ndarray, core: jnp.ndarray, n_slots: int,
                    block: int = 4096):
    """Connectivity of the core rows over the bucket graph.

    slots: (n * t,) int32, the (n, t) bucket-slot ids of every row in row
           order, each below ``n_slots``
    core:  (n,) bool, the rows that are core points
    returns (least, rounds): (n_slots,) int32, for each slot the least
    core row of its component (``n`` where the component holds no core
    row), and the int32 number of hook rounds.  A core row's component
    is that of its first slot.

    Two core rows connect exactly when they share a slot, so their
    components are those of the graph on the slots in which each core row
    joins its ``t`` slots.  Shiloach-Vishkin runs there: a round reads the
    root of each slot of a row, hooks each of those roots to the least of
    them, and jumps pointers until every slot points at its root.  A
    gather or scatter costs the chip per element, so the rounds touch as
    few rows as they can, and none past the last core row:

      * the first round needs no read, as every slot starts as its own
        root: each row's slots hook to its least slot;
      * one read of every row then finds the rows whose slots still see
        more than one root, and packs them to the front;
      * later rounds visit those rows alone, until none sees two roots.
        Hooks only ever give a root a parent, so a row once settled stays
        settled.

    The fewer rows the first round leaves, the less the rest costs; slot
    ids that put each cluster's fullest bucket first leave few.  The rows
    go ``block`` at a time, on the (t, n) view, whose long axis is minor
    so that no temporary pads the short one to a full tile.
    """
    n = core.shape[0]
    t = slots.shape[0] // n
    out = n_slots                        # reads give it, writes drop it
    s = jnp.where(core[None, :], slots.reshape(n, t).T, out)     # (t, n)
    rows = jnp.arange(n, dtype=jnp.int32)
    size = min(n, block)
    live = -(-jnp.max(jnp.where(core, rows + 1, 0)) // size)

    def rows_of(c):
        lo = jnp.minimum(c * size, n - size)
        return jax.lax.dynamic_slice(s, (0, lo), (t, size)), lo

    def read(p, i):
        return p.at[i].get(mode="fill", fill_value=out)

    def hook(p, r):
        return p.at[r].min(jnp.broadcast_to(r.min(axis=0), r.shape),
                           mode="drop")

    def jump(p):
        def step(c):
            p, _ = c
            pp = p[p]
            return pp, jnp.any(pp != p)

        return jax.lax.while_loop(lambda c: c[1], step, (p, True))[0]

    def first_round(c, carry):
        p, least = carry
        r, lo = rows_of(c)
        return hook(p, r), least.at[r[0]].min(lo + rows[:size], mode="drop")

    p, least = jax.lax.fori_loop(
        0, live, first_round,
        (jnp.arange(n_slots, dtype=jnp.int32),
         jnp.full((n_slots,), n, jnp.int32)))
    p = jump(p)

    def check(c, open_):
        r, lo = rows_of(c)
        r = read(p, r)
        return jax.lax.dynamic_update_slice(
            open_, jnp.any(r != r.min(axis=0), axis=0), (lo,))

    open_ = jax.lax.fori_loop(0, live, check, jnp.zeros((n,), bool))
    count = jnp.sum(open_, dtype=jnp.int32)
    packed = jnp.full((n,), n, jnp.int32).at[
        jnp.where(open_, _rank(open_), n)].set(rows, mode="drop")

    def settle(c, carry):
        p0, p, unsettled = carry
        i = jax.lax.dynamic_slice(packed, (jnp.minimum(c * size, n - size),),
                                  (size,))
        r = read(p0, s.at[:, i].get(mode="fill", fill_value=out))
        return p0, hook(p, r), unsettled | jnp.any(r != r.min(axis=0))

    def sv_round(c):
        p, rounds, _ = c
        _, p, unsettled = jax.lax.fori_loop(0, -(-count // size), settle,
                                            (p, p, False))
        return jump(p), rounds + unsettled, unsettled

    p, rounds, _ = jax.lax.while_loop(
        lambda c: c[2], sv_round, (p, (live > 0).astype(jnp.int32), count > 0))
    least = jnp.full((n_slots,), n, jnp.int32).at[p].min(least)
    return least[p], rounds


def _rank(flags: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix count of a (n,) bool vector: for each entry, how
    many set entries precede it.  Within each run of 128 a triangular
    matmul counts (exact: 0/1 inputs, sums below 2^24), across the runs a
    cumsum of n / 128 totals; a cumsum over all n entries takes the TPU's
    compiler seconds."""
    n = flags.shape[0]
    w = jnp.pad(flags, (0, -n % 128)).reshape(-1, 128).astype(jnp.float32)
    before = jnp.triu(jnp.ones((128, 128), jnp.float32), 1)
    inner = jnp.dot(w, before, precision=jax.lax.Precision.HIGHEST)
    tot = jnp.sum(w, axis=1).astype(jnp.int32)
    off = jnp.cumsum(tot) - tot
    return (inner.astype(jnp.int32) + off[:, None]).reshape(-1)[:n]


def eps_neighbor_counts(x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """|B(x_i, eps)| per point (self included), O(n^2 d)."""
    sq = jnp.sum(x * x, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (x @ x.T)
    return jnp.sum(d2 <= eps * eps + 1e-6, axis=-1).astype(jnp.int32)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference GQA attention.

    q: (b, hq, sq, dh); k, v: (b, hkv, skv, dh) with hq % hkv == 0.
    ``q_offset``: absolute position of q[0] (for decode: skv - sq).
    ``window``: sliding-window size (keys with q_pos - k_pos >= window are
    masked); None = full.
    """
    b, hq, sq, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * scale
    q_pos = jnp.arange(sq)[:, None] + q_offset
    k_pos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)
