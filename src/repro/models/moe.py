"""Mixture-of-Experts FFN (DeepSeek-style: shared + routed top-k).

Three interchangeable implementations (cfg.moe_impl):

* ``dense``  — every expert computed for every token, combined by gate
               weights.  O(E/k) FLOP waste; only for tiny smoke configs.
* ``tp``     — tensor-parallel MoE: activations are replicated over the
               "model" axis, experts are sharded over it.  Dispatch is a
               *local* capacity scatter on each shard (zero communication);
               combine is a psum over "model" (the same all-reduce any TP
               layer needs).  Default for the dry-run cells.
* ``ep_a2a`` — true expert parallelism: experts sharded over the token
               ("data") axis, dispatch/combine via lax.all_to_all.  This is
               the DeepSeek deployment style and produces the All-To-All
               network traffic the paper studies.  Selectable per config.

Token-choice top-k routing with per-expert capacity dropping (GShard);
gates renormalized over the kept top-k.  Dispatch never materializes the
(T*k, D) repeated-token tensor: tokens are scattered slot-by-slot (k small
scatters of (T, D)) into the capacity buffer.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.common.pytree import ParamDef


def moe_defs(cfg) -> dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    d = {
        "router": ParamDef((D, E), ("embed", None), init="scaled"),
        "w1": ParamDef((E, D, F), ("expert", "embed", "mlp"), init="scaled"),
        "w3": ParamDef((E, D, F), ("expert", "embed", "mlp"), init="scaled"),
        "w2": ParamDef((E, F, D), ("expert", "mlp", "embed"), init="scaled"),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        d["shared"] = {
            "w1": ParamDef((D, Fs), ("embed", "mlp"), init="scaled"),
            "w3": ParamDef((D, Fs), ("embed", "mlp"), init="scaled"),
            "w2": ParamDef((Fs, D), ("mlp", "embed"), init="scaled"),
        }
    return d


def _router(router_w, x, cfg):
    """x: (T, D) -> (gates, idx): (T, k).  fp32 routing."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32)) * cfg.router_scale
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _expert_ffn(w1, w3, w2, xb):
    """xb: (E_loc, C, D); weights (E_loc, D, F)/(E_loc, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", xb, w1.astype(xb.dtype))
    g = jnp.einsum("ecd,edf->ecf", xb, w3.astype(xb.dtype))
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(xb.dtype))


def _shared_ffn(p, x):
    h = jax.nn.silu(x @ p["w1"].astype(x.dtype)) * (x @ p["w3"].astype(x.dtype))
    return h @ p["w2"].astype(x.dtype)


# ---------------------------------------------------------------------------
# slot-wise capacity dispatch helpers
# ---------------------------------------------------------------------------

def _positions(idx2d, keep2d, n_buckets, cap):
    """Per-(token,slot) position within its destination bucket.

    idx2d/keep2d: (T, k) -> (pos2d, kept2d), row-major arrival order.
    """
    T, k = idx2d.shape
    flat = idx2d.reshape(-1)
    keep = keep2d.reshape(-1)
    oh = jax.nn.one_hot(flat, n_buckets, dtype=jnp.int32) * keep.astype(jnp.int32)[:, None]
    pre = jnp.cumsum(oh, axis=0) - oh
    pos = (pre * oh).sum(-1)
    kept = keep & (pos < cap)
    return pos.reshape(T, k), kept.reshape(T, k)


def _scatter_slots(x, idx2d, pos2d, kept2d, n_buckets, cap):
    """k scatters of (T, D) rows into (n_buckets, cap, D) — no (T*k, D)."""
    buf = jnp.zeros((n_buckets, cap, x.shape[-1]), x.dtype)
    for j in range(idx2d.shape[1]):
        buf = buf.at[idx2d[:, j], pos2d[:, j]].add(
            x * kept2d[:, j, None].astype(x.dtype), mode="drop")
    return buf


def _gather_slots(y, idx2d, pos2d, kept2d, gates):
    """Inverse of _scatter_slots, weighted by gates: (T, D)."""
    out = jnp.zeros((idx2d.shape[0], y.shape[-1]), y.dtype)
    for j in range(idx2d.shape[1]):
        w = (kept2d[:, j].astype(y.dtype) * gates[:, j].astype(y.dtype))[:, None]
        out = out + y[idx2d[:, j], pos2d[:, j]] * w
    return out


# ---------------------------------------------------------------------------
# dense fallback (smoke tests)
# ---------------------------------------------------------------------------

def _moe_dense(p, x, cfg):
    gates, idx = _router(p["router"], x, cfg)
    h = jnp.einsum("td,edf->tef", x, p["w1"].astype(x.dtype))
    g = jnp.einsum("td,edf->tef", x, p["w3"].astype(x.dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * g, p["w2"].astype(x.dtype))
    sel = jnp.take_along_axis(y, idx[:, :, None], axis=1)  # (T,k,D)
    return (sel * gates[:, :, None].astype(x.dtype)).sum(1)


# ---------------------------------------------------------------------------
# TP MoE: experts over "model", tokens replicated over "model"
# ---------------------------------------------------------------------------

def _moe_tp_local(router_w, w1, w3, w2, x, *, cfg, n_model, model_axis):
    """Per-shard body (inside shard_map).  x: (T_loc, D) replicated over
    ``model_axis``; w*: local expert slices (E_loc, ...)."""
    E = cfg.n_experts
    E_loc = E // n_model
    my = lax.axis_index(model_axis)
    gates, idx = _router(router_w, x, cfg)  # full-E routing, identical on shards

    mine = (idx >= my * E_loc) & (idx < (my + 1) * E_loc)
    e_local = jnp.clip(idx - my * E_loc, 0, E_loc - 1)
    Tk = idx.size
    cap = max(1, int(cfg.capacity_factor * Tk / max(n_model * E_loc, 1)))

    pos, kept = _positions(e_local, mine, E_loc, cap)
    buf = _scatter_slots(x, e_local, pos, kept, E_loc, cap)
    y = _expert_ffn(w1, w3, w2, buf)
    out = _gather_slots(y, e_local, pos, kept, gates)
    return lax.psum(out, model_axis)


# ---------------------------------------------------------------------------
# EP MoE: experts over "data", dispatch via all_to_all
# ---------------------------------------------------------------------------

def _moe_ep_local(router_w, w1, w3, w2, x, *, cfg, n_data, data_axis, model_axis):
    """Per-shard body.  x: (T_loc, D) sharded over ``data_axis``; experts
    sharded over the same axis (E_loc per shard); expert d_ff sharded over
    ``model_axis`` (TP-within-expert, psum combine).  Dispatch + combine are
    each one lax.all_to_all over ``data_axis`` — the paper's A2A traffic."""
    E = cfg.n_experts
    E_loc = E // n_data
    gates, idx = _router(router_w, x, cfg)
    dst = idx // E_loc                       # destination data shard (T,k)
    Tk = idx.size
    cap = max(1, int(cfg.capacity_factor * Tk / n_data))

    pos, kept = _positions(dst, jnp.ones_like(dst, bool), n_data, cap)
    send = _scatter_slots(x, dst, pos, kept, n_data, cap)
    # metadata rides along: local expert id within destination, +1 so that
    # empty slots (0) mark invalid rows after the exchange.
    meta = jnp.zeros((n_data, cap), jnp.int32)
    for j in range(idx.shape[1]):
        meta = meta.at[dst[:, j], pos[:, j]].add(
            jnp.where(kept[:, j], idx[:, j] % E_loc + 1, 0), mode="drop")

    recv = lax.all_to_all(send, data_axis, split_axis=0, concat_axis=0, tiled=True)
    meta_r = lax.all_to_all(meta, data_axis, split_axis=0, concat_axis=0, tiled=True)

    rows = recv.reshape(-1, x.shape[-1])            # (n_data*cap, D)
    e_of_row = meta_r.reshape(-1)                   # 0 = empty, else e_local+1
    valid = (e_of_row > 0)[:, None]
    e_row = jnp.clip(e_of_row - 1, 0, E_loc - 1)[:, None]
    cap2 = max(1, int(cfg.capacity_factor * rows.shape[0] / max(E_loc, 1)))
    pos2, kept2 = _positions(e_row, valid, E_loc, cap2)
    buf = _scatter_slots(rows, e_row, pos2, kept2, E_loc, cap2)
    y = _expert_ffn(w1, w3, w2, buf)                # partial over model (F sharded)
    y = lax.psum(y, model_axis)
    ones = jnp.ones((rows.shape[0], 1), y.dtype)
    back_rows = _gather_slots(y, e_row, pos2, kept2, ones)
    back = back_rows.reshape(n_data, cap, x.shape[-1])
    ret = lax.all_to_all(back, data_axis, split_axis=0, concat_axis=0, tiled=True)
    return _gather_slots(ret, dst, pos, kept, gates)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def moe_apply(p, x2d, cfg, mesh=None):
    """x2d: (T, D) -> (T, D).  Routed experts + shared experts."""
    impl = cfg.moe_impl
    if mesh is None or impl == "dense" or "model" not in getattr(mesh, "axis_names", ()):
        routed = _moe_chunked(lambda xs: _moe_dense(p, xs, cfg), x2d, cfg, mesh)
    elif impl == "tp":
        n_model = mesh.shape["model"]
        batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        fn = jax.shard_map(
            partial(_moe_tp_local, cfg=cfg, n_model=n_model, model_axis="model"),
            mesh=mesh,
            in_specs=(P(None, None), P("model", None, None), P("model", None, None),
                      P("model", None, None), P(batch_axes, None)),
            out_specs=P(batch_axes, None),
            check_vma=False,
        )
        routed = _moe_chunked(
            lambda xs: fn(p["router"], p["w1"], p["w3"], p["w2"], xs), x2d, cfg, mesh)
    elif impl == "ep_a2a":
        n_data = mesh.shape["data"]
        batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        fn = jax.shard_map(
            partial(_moe_ep_local, cfg=cfg, n_data=n_data, data_axis="data",
                    model_axis="model"),
            mesh=mesh,
            in_specs=(P(None, None), P("data", None, "model"), P("data", None, "model"),
                      P("data", "model", None), P(batch_axes, None)),
            out_specs=P(batch_axes, None),
            check_vma=False,
        )
        routed = _moe_chunked(
            lambda xs: fn(p["router"], p["w1"], p["w3"], p["w2"], xs), x2d, cfg, mesh)
    else:
        raise ValueError(f"unknown moe_impl {impl}")

    if cfg.n_shared_experts:
        routed = routed + _shared_ffn(p["shared"], x2d)
    return routed


def _batch_shards(mesh) -> int:
    if mesh is None:
        return 1
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= int(mesh.shape[a])
    return n


def _moe_chunked(fn, x2d, cfg, mesh=None):
    """Process tokens in cfg.moe_chunks microchunks to bound dispatch
    buffer memory (DESIGN.md §5).  Chunks must stay divisible by the
    token-sharding factor, so n is reduced as needed."""
    n = cfg.moe_chunks
    T = x2d.shape[0]
    shards = _batch_shards(mesh)
    while n > 1 and (T % n != 0 or (T // n) % shards != 0):
        n //= 2
    if n <= 1:
        return fn(x2d)
    xc = x2d.reshape(n, T // n, -1)
    yc = lax.map(fn, xc)
    return yc.reshape(T, -1)


# EP sharding overrides for ep_a2a mode (expert dim over data, F over model)
def moe_param_overrides(cfg) -> dict | None:
    """Sharding-rule overrides needed by the chosen impl."""
    if cfg.moe_impl == "ep_a2a":
        return {"expert": ("data",)}
    return None
