"""GQA attention block: chunked (flash-style) jnp path + decode path.

The jnp chunked path is the portable implementation the dry-run lowers
(online softmax over q-chunks, O(chunk · kv) live memory); the Pallas
kernel `repro.kernels.flash_attention` is its blocked counterpart, not
called from here.  Decode attends one token against a (possibly
sequence-sharded) KV cache; softmax/contraction over the sharded axis
lowers to small all-reduces under GSPMD (DESIGN.md §4).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding import shard_activation
from . import layers as L

NEG_INF = -1e30


def init_attention(key, cfg, dtype=jnp.float32):
    E = cfg.d_model
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    std = L.fan_in_std(E)
    decls = {
        "wq": ((E, Hq, Dh), ("embed", "heads", "head_dim"), std),
        "wk": ((E, Hkv, Dh), ("embed", "kv_heads", "head_dim"), std),
        "wv": ((E, Hkv, Dh), ("embed", "kv_heads", "head_dim"), std),
        "wo": ((Hq, Dh, E), ("heads", "head_dim", "embed"), L.fan_in_std(Hq * Dh)),
    }
    if cfg.qkv_bias:
        decls.update({
            "bq": ((Hq, Dh), ("heads", "head_dim"), 0.0),
            "bk": ((Hkv, Dh), ("kv_heads", "head_dim"), 0.0),
            "bv": ((Hkv, Dh), ("kv_heads", "head_dim"), 0.0),
        })
    return L.declare(key, decls, dtype)


def _project_qkv(p, x, cfg, compute_dtype):
    q = jnp.einsum("bse,ehd->bhsd", x, p["wq"].astype(compute_dtype))
    k = jnp.einsum("bse,ehd->bhsd", x, p["wk"].astype(compute_dtype))
    v = jnp.einsum("bse,ehd->bhsd", x, p["wv"].astype(compute_dtype))
    if "bq" in p:
        q = q + p["bq"].astype(compute_dtype)[None, :, None, :]
        k = k + p["bk"].astype(compute_dtype)[None, :, None, :]
        v = v + p["bv"].astype(compute_dtype)[None, :, None, :]
    return q, k, v


def chunked_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = True, window=None, chunk: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """q: (b, hq, sq, dh); k, v: (b, hkv, skv, dh).  ``window`` may be a
    traced scalar (per-layer metadata inside scans); <= 0 means full."""
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    chunk = min(chunk, sq)
    pad = -sq % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nq = (sq + pad) // chunk
    qc = q.reshape(b, hkv, g, nq, chunk, dh).transpose(3, 0, 1, 2, 4, 5)
    k_pos = jnp.arange(skv)

    win = jnp.asarray(-1 if window is None else window, jnp.int32)

    def one_chunk(ci, qi):
        # qi: (b, hkv, g, chunk, dh)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        q_pos = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, skv), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        mask &= jnp.where(
            win > 0, (q_pos[:, None] - k_pos[None, :]) < win, True
        )
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)

    out = jax.lax.map(lambda args: one_chunk(*args),
                      (jnp.arange(nq), qc))
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, sq + pad, dh)
    return out[:, :, :sq]


def attention_block(
    p: Dict[str, Any], x: jnp.ndarray, cfg, *,
    theta, window, compute_dtype, positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
) -> jnp.ndarray:
    """Full-sequence (train/prefill) attention block."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, compute_dtype)
    q = shard_activation(q, ("batch", "heads", None, None))
    k = shard_activation(k, ("batch", "kv_heads", None, None))
    v = shard_activation(v, ("batch", "kv_heads", None, None))
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if theta is not None:
        q = L.rope(q, positions[:, None, :], theta)
        k = L.rope(k, positions[:, None, :], theta)
    out = chunked_attention(
        q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk
    )
    out = shard_activation(out, ("batch", "heads", None, None))
    return jnp.einsum("bhsd,hde->bse", out, p["wo"].astype(compute_dtype))


# --------------------------------------------------------------------- #
# decode path
# --------------------------------------------------------------------- #
def init_kv_cache(cfg, batch: int, kv_len: int, n_layers: int, dtype):
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, batch, Hkv, kv_len, Dh)
    axes = ("layers", "cache_batch", "kv_heads", "cache_seq", "head_dim")
    return (
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)},
        {"k": axes, "v": axes},
    )


def decode_attention_block(
    p: Dict[str, Any], x: jnp.ndarray, cache_k, cache_v, pos, cfg, *,
    theta, window, compute_dtype, windowed_cache: bool = False,
    active: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode.  x: (b, 1, E); cache_k/v: (b, hkv, S, dh).

    ``pos``: scalar int32 or per-row (b,) int32 — absolute position of each
    row's new token (continuous batching).  ``active``: optional (b,) bool;
    inactive rows leave their cache untouched.

    Full cache: written at slot pos[i] per row.  Windowed cache (gemma3
    local layers): shift-left ring of size W — requires a uniform scalar
    ``pos`` (batch-synchronous decode).
    """
    b = x.shape[0]
    S = cache_k.shape[2]
    q, k, v = _project_qkv(p, x, cfg, compute_dtype)  # (b, h, 1, dh)
    pos_vec = jnp.broadcast_to(jnp.atleast_1d(pos), (b,)).astype(jnp.int32)
    posv = pos_vec[:, None, None]
    if theta is not None:
        q = L.rope(q, posv, theta)
        k = L.rope(k, posv, theta)
    if active is None:
        act = jnp.ones((b,), bool)
    else:
        act = active

    if windowed_cache:
        new_k = jnp.roll(cache_k, -1, axis=2)
        new_v = jnp.roll(cache_v, -1, axis=2)
        new_k = jax.lax.dynamic_update_slice(new_k, k, (0, 0, S - 1, 0))
        new_v = jax.lax.dynamic_update_slice(new_v, v, (0, 0, S - 1, 0))
        # slot j holds absolute position pos - (S-1-j)
        k_pos = pos_vec[:, None] - (S - 1 - jnp.arange(S))[None, :]
        valid = k_pos >= 0
    elif jnp.ndim(pos) == 0:
        # batch-synchronous decode (the dry-run/serve_step fast path):
        # dynamic_update_slice on the seq-sharded cache lowers to a masked
        # local update under GSPMD — a per-row scatter would all-gather
        # the whole cache (measured: 25 GB/step on qwen decode_32k)
        new_k = jax.lax.dynamic_update_slice(
            cache_k, k, (0, 0, pos.astype(jnp.int32), 0)
        )
        new_v = jax.lax.dynamic_update_slice(
            cache_v, v, (0, 0, pos.astype(jnp.int32), 0)
        )
        k_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (b, S))
        valid = k_pos <= pos_vec[:, None]
        if window is not None:
            valid &= (pos_vec[:, None] - k_pos) < jnp.asarray(window)
    else:
        # continuous batching: per-row positions
        idx = jnp.arange(b)
        new_k = cache_k.at[idx, :, pos_vec, :].set(k[:, :, 0, :])
        new_v = cache_v.at[idx, :, pos_vec, :].set(v[:, :, 0, :])
        k_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (b, S))
        valid = k_pos <= pos_vec[:, None]
        if window is not None:
            valid &= (pos_vec[:, None] - k_pos) < jnp.asarray(window)
    sel = act[:, None, None, None]
    cache_k = jnp.where(sel, new_k, cache_k)
    cache_v = jnp.where(sel, new_v, cache_v)

    scale = 1.0 / (cfg.resolved_head_dim ** 0.5)
    hq, hkv = q.shape[1], cache_k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, cache_k.shape[-1])
    s = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32),
                   cache_k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", pr.astype(cache_v.dtype), cache_v)
    out = out.reshape(b, 1, hq, cache_k.shape[-1]).transpose(0, 2, 1, 3)
    y = jnp.einsum("bhsd,hde->bse", out, p["wo"].astype(compute_dtype))
    return y, cache_k, cache_v
