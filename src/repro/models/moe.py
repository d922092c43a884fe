"""Mixture-of-Experts FFN: top-k router, capacity-bounded scatter dispatch,
optional shared experts (DeepSeek), softmax or sigmoid gating.

Dispatch is scatter/gather (linear memory), not the (T, E, C) one-hot
einsum: token t's k-th assignment lands at flat slot e*C + position-in-
expert, positions computed by a cumulative count over the (T*k, E)
assignment matrix.  Expert weights live on the 'experts' logical axis
(sharded over 'model' when E divides the axis — expert parallelism);
GSPMD then materializes the all-to-all-shaped collectives the roofline
tracks.  Aux load-balance loss is the switch-style f*P product.

DeepSeek-V3's bias-based aux-free balancing is replaced by the standard
aux loss (documented deviation; the routing math — sigmoid scores,
top-k over scores, normalization over the selected k — is V3-faithful).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import shard
from .layers import _act
from .params import dense_init

__all__ = ["init_moe", "apply_moe"]


def init_moe(cfg, key, spec):
    moe = spec.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    e, f = moe.num_experts, moe.d_ff
    p = {
        "router": dense_init(ks[0], (d, e), ("embed", "experts")),
        "wi": dense_init(ks[1], (e, d, f), ("experts", "embed", "expert_mlp")),
        "wg": dense_init(ks[2], (e, d, f), ("experts", "embed", "expert_mlp")),
        "wo": dense_init(ks[3], (e, f, d), ("experts", "expert_mlp", "embed")),
    }
    if moe.num_shared:
        sub = jax.random.split(ks[4], 3)
        fs = moe.d_ff * moe.num_shared
        p["shared"] = {
            "wi": dense_init(sub[0], (d, fs), ("embed", "mlp")),
            "wg": dense_init(sub[1], (d, fs), ("embed", "mlp")),
            "wo": dense_init(sub[2], (fs, d), ("mlp", "embed")),
        }
    return p


def _capacity(n_tokens: int, moe) -> int:
    cap = int(np.ceil(n_tokens * moe.top_k * moe.capacity_factor / moe.num_experts))
    return max(8, -(-cap // 8) * 8)  # multiple of 8 for layout sanity


def apply_moe(cfg, p, x, spec):
    """x: (B, S, d) -> (out, aux_loss).  Dispatches to the GSPMD path or
    the manual shard_map path per cfg.moe_impl."""
    if getattr(cfg, "moe_impl", "gspmd") == "manual":
        out = _apply_moe_manual(cfg, p, x, spec)
        if out is not None:
            return out
    return _moe_core(cfg, p, x, spec)


def _apply_moe_manual(cfg, p, x, spec):
    """Beyond-GSPMD MoE: shard_map over the batch axes with LOCAL
    capacity.  Dispatch/combine never leave the device; the only
    collectives are the (auto-sharded) expert-weight contractions.
    Avoids GSPMD's involuntary replication of the (E, C_global, d)
    dispatch buffer when E does not divide the model axis (mixtral's
    8 experts on a 16-way axis).  Returns None to fall back when no
    mesh is active or the batch does not shard.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import (current_mesh, current_rules, strip_rules,
                                     use_mesh)

    mesh = current_mesh()
    if mesh is None:
        return None
    rules = current_rules()
    b = x.shape[0]
    batch_axes = []
    size = 1
    for a in rules.get("batch", ()):
        if a in mesh.shape and b % (size * mesh.shape[a]) == 0:
            batch_axes.append(a)
            size *= mesh.shape[a]
    if size <= 1:
        return None
    inner_rules = strip_rules(rules, set(batch_axes))
    axes_t = tuple(batch_axes)

    def local_fn(x_loc, p_loc):
        with use_mesh(mesh, inner_rules, manual=True):
            out, aux = _moe_core(cfg, p_loc, x_loc, spec)
            aux = jax.lax.pmean(aux, axes_t)
            return out, aux

    smapped = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axes_t), P()),
        out_specs=(P(axes_t), P()),
        axis_names=set(batch_axes),
        check_vma=False,
    )
    return smapped(x, p)


def _moe_core(cfg, p, x, spec):
    moe = spec.moe
    b, s, d = x.shape
    dt = x.dtype
    t = b * s
    xt = x.reshape(t, d)
    e, k = moe.num_experts, moe.top_k

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dt)).astype(jnp.float32)
    if moe.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gate_vals, idx = jax.lax.top_k(scores, k)  # (t, k)
        gates = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, k)
        gates = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (switch-style): E * sum_e f_e * P_e
    assign_1h = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)  # top-1 fraction
    f_e = assign_1h.mean(axis=0)
    p_e = probs.mean(axis=0)
    aux = moe.aux_loss_coef * e * jnp.sum(f_e * p_e)

    # ---- capacity positions over flattened (t*k) assignment stream
    cap = _capacity(t, moe)
    flat_e = idx.reshape(t * k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (t*k, e)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (t*k,)
    keep = (pos < cap).astype(dt)
    dest = flat_e * cap + jnp.minimum(pos, cap - 1)  # clamped (dropped are zeroed)

    tok_idx = jnp.repeat(jnp.arange(t), k)
    gathered = xt[tok_idx] * keep[:, None]  # (t*k, d)
    buf = jnp.zeros((e * cap, d), dt).at[dest].add(gathered)
    buf = shard(buf.reshape(e, cap, d), "experts", None, None)

    # ---- expert FFN (gated)
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt))
    h = _act(cfg, g) * h
    h = shard(h, "experts", None, "expert_mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt)).reshape(e * cap, d)

    # ---- combine
    back = out_buf[dest] * (keep * gates.reshape(t * k))[:, None]  # (t*k, d)
    combined = jnp.zeros((t, d), dt).at[tok_idx].add(back)
    out = combined.reshape(b, s, d)

    if "shared" in p:
        sp = p["shared"]
        hs = jnp.einsum("bsd,df->bsf", x, sp["wi"].astype(dt))
        gs = jnp.einsum("bsd,df->bsf", x, sp["wg"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", _act(cfg, gs) * hs, sp["wo"].astype(dt))

    return shard(out, "batch", "seq", "embed"), aux
