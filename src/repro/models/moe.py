"""Expert-parallel MoE block (shard_map + all_to_all dispatch).

Routing is computed locally per data shard; tokens are capacity-padded into
an (experts, capacity, d_model) buffer and exchanged with the expert owners
via ``lax.all_to_all`` over the ``model`` axis — the canonical EP collective
pattern.  Requires n_experts % model_axis == 0; otherwise (and on meshes
without a ``model`` axis) the exact dense-dispatch reference below is used,
which is also the test oracle.

Capacity drops follow the standard top-k-then-truncate rule; the combine is
a weighted scatter-add, so dropped tokens contribute zero (residual carries
them).  An auxiliary load-balancing loss (Shazeer-style) is returned for
the trainer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from . import layers as L


def init_moe(key, cfg, dtype=jnp.float32):
    E, F, X = cfg.d_model, cfg.d_ff, cfg.n_experts
    std = L.fan_in_std(E)
    return L.declare(key, {
        "router": ((E, X), ("embed_r", None), std),
        "w_gate": ((X, E, F), ("experts", "embed", "mlp"), std),
        "w_up": ((X, E, F), ("experts", "embed", "mlp"), std),
        "w_down": ((X, F, E), ("experts", "mlp", "embed"), L.fan_in_std(F)),
    }, dtype)


def _expert_ffn(w_gate, w_up, w_down, x, compute_dtype, psum_axis=None):
    # x: (X_local, C, E) — E may be a local shard (weight-stationary
    # decode): contract the local slice and psum the partials.
    g = jnp.einsum("xce,xef->xcf", x, w_gate.astype(compute_dtype))
    u = jnp.einsum("xce,xef->xcf", x, w_up.astype(compute_dtype))
    if psum_axis is not None:
        g = jax.lax.psum(g, psum_axis)
        u = jax.lax.psum(u, psum_axis)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * u
    return jnp.einsum("xcf,xfe->xce", h, w_down.astype(compute_dtype))


def _aux_loss(probs: jnp.ndarray, expert_idx: jnp.ndarray, n_experts: int):
    """Load-balance loss: X * sum_e f_e * P_e (f = token fraction routed)."""
    X = n_experts
    one_hot = jax.nn.one_hot(expert_idx, X, dtype=jnp.float32)  # (..., k, X)
    f = one_hot.sum(axis=-2).reshape(-1, X).mean(axis=0)
    p = probs.reshape(-1, X).mean(axis=0)
    return X * jnp.sum(f * p)


def moe_block_dense(p, x, cfg, compute_dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact dense-dispatch reference: every expert sees every token."""
    probs = jax.nn.softmax(
        jnp.einsum("bse,ex->bsx", x.astype(jnp.float32),
                   p["router"].astype(jnp.float32)), axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None, None],
        jnp.arange(x.shape[1])[None, :, None],
        idx,
    ].set(vals)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    g = jnp.einsum("bse,xef->bsxf", x, p["w_gate"].astype(compute_dtype))
    u = jnp.einsum("bse,xef->bsxf", x, p["w_up"].astype(compute_dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * u
    y = jnp.einsum("bsxf,xfe->bsxe", h, p["w_down"].astype(compute_dtype))
    out = jnp.einsum("bsxe,bsx->bse", y, gates.astype(compute_dtype))
    return out, _aux_loss(probs, idx, cfg.n_experts)


def _local_dispatch_combine(p, x, cfg, compute_dtype, ep_size: int,
                            dp_axes: tuple, gather_axes: dict,
                            weight_stationary: bool = False):
    """Body run per (pod, data, model) shard inside shard_map.

    Two weight-consumption modes:
      * train/prefill: ZeRO-3 gather — expert weights arrive sharded over
        `data` on their embed/mlp dims; cast to compute dtype BEFORE the
        all-gather (bf16 wire/temp, 2x cheaper), gather, contract locally.
      * decode (weight_stationary): DON'T gather — x arrives with its
        embed dim sharded over `data`; contract the local E slice and
        psum partials.  Per-token weight movement drops from O(params) to
        O(activations) (EXPERIMENTS §Perf iteration 1c).
    """
    p = dict(p)
    psum_axis = None
    if weight_stationary:
        psum_axis = "data" if gather_axes else None
    else:
        for name, dim in gather_axes.items():
            p[name] = jax.lax.all_gather(
                p[name].astype(compute_dtype), "data", axis=dim, tiled=True
            )
    b, s, E = x.shape  # E is the LOCAL embed width in weight-stationary mode
    X, k = cfg.n_experts, cfg.top_k
    T = b * s
    xf = x.reshape(T, E)
    if psum_axis is not None:
        # router table is replicated; x's E dim is this shard's slice —
        # contract against the matching router rows and psum the partials
        idx = jax.lax.axis_index(psum_axis)
        router_rows = jax.lax.dynamic_slice_in_dim(
            p["router"].astype(jnp.float32), idx * E, E, 0
        )
        router_logits = jax.lax.psum(
            jnp.einsum("te,ex->tx", xf.astype(jnp.float32), router_rows),
            psum_axis,
        )
    else:
        router_logits = jnp.einsum(
            "te,ex->tx", xf.astype(jnp.float32), p["router"].astype(jnp.float32)
        )
    probs = jax.nn.softmax(router_logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)  # (T, k)
    vals = vals / (vals.sum(-1, keepdims=True) + 1e-9)
    aux = _aux_loss(probs, idx, X)
    aux = jax.lax.pmean(aux, dp_axes + ("model",) if dp_axes else ("model",))

    e_flat = idx.reshape(-1)                       # (T*k,)
    t_flat = jnp.repeat(jnp.arange(T), k)
    w_flat = vals.reshape(-1)
    order = jnp.argsort(e_flat)
    e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
    counts = jnp.bincount(e_flat, length=X)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[e_s]
    C = int(max(1, -(-T * k // X) * cfg.capacity_factor))
    keep = pos < C

    buf = jnp.zeros((X, C, E), compute_dtype)
    buf = buf.at[
        jnp.where(keep, e_s, 0), jnp.where(keep, pos, 0)
    ].add(jnp.where(keep[:, None], xf[t_s], 0).astype(compute_dtype))

    if ep_size > 1:
        # (X, C, E) -> (X/ep, C*ep, E): tokens for my experts from all peers
        buf = jax.lax.all_to_all(
            buf, "model", split_axis=0, concat_axis=1, tiled=True
        )
    h = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], buf, compute_dtype,
                    psum_axis=psum_axis)
    if ep_size > 1:
        h = jax.lax.all_to_all(
            h, "model", split_axis=1, concat_axis=0, tiled=True
        )
    # combine: weighted gather back to token order
    gathered = h[jnp.where(keep, e_s, 0), jnp.where(keep, pos, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.zeros((T, E), jnp.float32).at[t_s].add(
        gathered.astype(jnp.float32) * w_s[:, None]
    )
    return y.astype(compute_dtype).reshape(b, s, E), aux


def moe_block(p, x, cfg, compute_dtype, mesh: Mesh | None):
    """EP MoE; falls back to dense dispatch off-mesh or when experts don't
    divide the model axis."""
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return moe_block_dense(p, x, cfg, compute_dtype)
    ep = mesh.shape["model"]
    if cfg.n_experts % ep != 0:
        return moe_block_dense(p, x, cfg, compute_dtype)

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # in_specs must MATCH the storage sharding (experts -> model, embed/mlp
    # FSDP'd over data); a mismatch makes the SPMD partitioner insert
    # pathological reshards at the shard_map boundary.
    from ..sharding import logical_to_spec

    w_axes = {
        "w_gate": ("experts", "embed", "mlp"),
        "w_up": ("experts", "embed", "mlp"),
        "w_down": ("experts", "mlp", "embed"),
    }
    pspecs = {"router": P()}
    gather_axes = {}
    for name, axes in w_axes.items():
        spec = logical_to_spec(axes, p[name].shape, mesh)
        pspecs[name] = spec
        for dim, entry in enumerate(spec):
            entries = entry if isinstance(entry, tuple) else (entry,)
            if "data" in entries:
                gather_axes[name] = dim
    # Route only the local sequence slice per model shard: with tokens
    # replicated over `model`, every shard would route (and the expert
    # owners would compute) the SAME tokens ep× over — measured 16×
    # redundant expert FLOPs on dbrx-132b before this split.
    s = x.shape[1]
    seq_split = s % ep == 0 and s >= ep
    # decode (s == 1): weight-stationary mode — x carries the data-shard
    # of its embed dim; expert weights are never gathered (per-token
    # weight movement O(params) -> O(activations)).
    dsz = mesh.shape.get("data", 1)
    weight_stationary = (
        s == 1 and bool(gather_axes) and x.shape[-1] % dsz == 0 and dsz > 1
    )
    body = functools.partial(
        _local_dispatch_combine, cfg=cfg, compute_dtype=compute_dtype,
        ep_size=ep, dp_axes=dp_axes, gather_axes=gather_axes,
        weight_stationary=weight_stationary,
    )
    if weight_stationary:
        x_spec = P(None, None, "data")
    else:
        x_spec = P(dp_axes, "model" if seq_split else None, None)
    fn = shard_map(
        lambda pp, xx: body(pp, xx),
        mesh=mesh,
        in_specs=(pspecs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    y, aux = fn({k: p[k] for k in pspecs}, x)
    return y, aux
