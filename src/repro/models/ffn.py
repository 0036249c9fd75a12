"""FFN substrate: dense gated FFNs and top-k routed Mixture-of-Experts.

The MoE dispatch is sort-based (argsort by expert, capacity-bounded grouped
matmul) — no O(T·E·C) one-hot dispatch tensors, shards cleanly under EP
("experts" -> model axis) or expert-TP ("mlp" -> model axis) depending on
divisibility.  Note the conceptual tie to the paper: routed experts are
*statically-skipped weight blocks* — the MoE analogue of the zero-skipping
schedule in kernels/deconv2d_sparse.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.context import constrain, current
from . import nn


# ---------------------------------------------------------------------------
# Dense gated FFN
# ---------------------------------------------------------------------------
def ffn_init(key, d_model: int, d_ff: int, dtype, activation: str = "swiglu"):
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["wu"], s["wu"] = nn.dense_init(ks[0], d_model, d_ff, dtype, ("embed", "mlp"))
    p["wd"], s["wd"] = nn.dense_init(ks[1], d_ff, d_model, dtype, ("mlp", "embed"))
    if activation in ("swiglu", "geglu"):
        p["wg"], s["wg"] = nn.dense_init(ks[2], d_model, d_ff, dtype, ("embed", "mlp"))
    return p, s


def ffn_apply(p: nn.Params, x: jax.Array, activation: str = "swiglu") -> jax.Array:
    if activation == "swiglu":
        h = nn.silu(nn.dense(p["wg"], x)) * nn.dense(p["wu"], x)
    elif activation == "geglu":
        h = nn.gelu(nn.dense(p["wg"], x)) * nn.dense(p["wu"], x)
    else:  # gelu
        h = nn.gelu(nn.dense(p["wu"], x))
    return nn.dense(p["wd"], h)


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------
def moe_init(key, cfg, dtype):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["router"], s["router"] = nn.dense_init(
        ks[0], d, e, dtype, ("embed", None)
    )
    p["wg"] = nn.lecun_init(ks[1], (e, d, f), dtype, fan_in=d)
    p["wu"] = nn.lecun_init(ks[2], (e, d, f), dtype, fan_in=d)
    p["wd"] = nn.lecun_init(ks[3], (e, f, d), dtype, fan_in=f)
    s["wg"] = ("experts", "embed", "mlp")
    s["wu"] = ("experts", "embed", "mlp")
    s["wd"] = ("experts", "mlp", "embed")
    if cfg.n_shared_experts > 0:
        sf = cfg.n_shared_experts * cfg.expert_d_ff
        p["shared"], s["shared"] = ffn_init(ks[4], d, sf, dtype, "swiglu")
        p["shared_gate"], s["shared_gate"] = nn.dense_init(
            ks[5], d, 1, dtype, ("embed", None)
        )
    return p, s


def _dispatch_groups(t: int) -> int:
    """Shard-local dispatch groups: each group's scatter/gather stays on its
    own data shard (no replicate-and-all-reduce lowering).  32 covers the
    multi-pod DP degree; tiny token counts (tests) use a single group."""
    for g in (32, 16, 8, 4, 2):
        if t % g == 0 and t // g >= 64:
            return g
    return 1


def moe_apply(
    p: nn.Params, cfg, x: jax.Array, capacity_factor: float = 1.25
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), load-balancing aux loss scalar).

    Sort-based capacity dispatch performed independently per token group
    (group dim sharded over 'data'): scatters and gathers are shard-local;
    inter-shard traffic is only the expert weights (expert-TP) or the
    grouped activations entering EP expert shards."""
    b, sl, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    t = b * sl
    xf = x.reshape(t, d)

    logits = (xf @ p["router"]["w"]).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                  # (T, k)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    g = _dispatch_groups(t)
    tg = t // g
    cap = int(max(1, round(tg * k / e * capacity_factor)))
    xg = xf.reshape(g, tg, d)
    xg = constrain(xg, "moe_group", None, None)

    flat_e = top_e.reshape(g, tg * k)
    sort_idx = jnp.argsort(flat_e, axis=1)                  # (G, Tg*k)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=1)
    counts = jax.vmap(lambda f: jnp.bincount(f, length=e))(flat_e)  # (G, E)
    offsets = jnp.cumsum(counts, axis=1) - counts           # (G, E)
    pos_in_e = (jnp.arange(tg * k)[None, :]
                - jnp.take_along_axis(offsets, sorted_e, axis=1))
    keep = pos_in_e < cap
    pos_safe = jnp.where(keep, pos_in_e, cap)               # cap = OOB drop
    src_tok = sort_idx // k                                 # (G, Tg*k)

    # Shard-local scatter/gather: XLA's scatter partitioner replicates the
    # (G, Tg*k, D) intermediates under pjit auto-sharding; shard_map over the
    # group axis makes the dispatch provably local to each data shard.
    mesh, rules = current()
    dp_axis = (rules or {}).get("moe_group")
    use_sm = (mesh is not None and dp_axis in getattr(mesh, "shape", {})
              and g % mesh.shape[dp_axis] == 0)

    def _scatter_local(xg_l, se_l, ps_l, st_l):
        gl = xg_l.shape[0]
        gi = jnp.arange(gl)[:, None]
        upd = jnp.take_along_axis(xg_l, st_l[..., None], axis=1)
        hb = jnp.zeros((gl, e, cap, d), xg_l.dtype)
        return hb.at[gi, se_l, ps_l].set(upd, mode="drop")

    if use_sm:
        hbuf = jax.shard_map(
            _scatter_local, mesh=mesh,
            in_specs=(P(dp_axis), P(dp_axis), P(dp_axis), P(dp_axis)),
            out_specs=P(dp_axis), check_vma=False,
        )(xg, sorted_e, pos_safe, src_tok)
    else:
        hbuf = _scatter_local(xg, sorted_e, pos_safe, src_tok)
    hbuf = constrain(hbuf, "moe_group", "experts", None, None)

    # ---- grouped expert FFN (SwiGLU) --------------------------------------
    hg = jnp.einsum("gecd,edf->gecf", hbuf, p["wg"])
    hu = jnp.einsum("gecd,edf->gecf", hbuf, p["wu"])
    hh = nn.silu(hg) * hu
    hh = constrain(hh, "moe_group", "experts", None, "mlp")
    out_e = jnp.einsum("gecf,efd->gecd", hh, p["wd"])
    out_e = constrain(out_e, "moe_group", "experts", None, None)

    # ---- combine -----------------------------------------------------------
    w_sorted = jnp.take_along_axis(
        top_p.reshape(g, tg * k), sort_idx, axis=1).astype(x.dtype)

    def _combine_local(oe_l, se_l, ps_l, st_l, ws_l):
        gl = oe_l.shape[0]
        gi = jnp.arange(gl)[:, None]
        gat = oe_l.at[gi, se_l, ps_l].get(mode="fill", fill_value=0)
        yl = jnp.zeros((gl, tg, d), jnp.float32)
        return yl.at[gi, st_l].add(
            (gat * ws_l[..., None]).astype(jnp.float32))

    if use_sm:
        y = jax.shard_map(
            _combine_local, mesh=mesh,
            in_specs=(P(dp_axis),) * 5,
            out_specs=P(dp_axis), check_vma=False,
        )(out_e, sorted_e, pos_safe, src_tok, w_sorted)
    else:
        y = _combine_local(out_e, sorted_e, pos_safe, src_tok, w_sorted)
    y = y.reshape(t, d).astype(x.dtype)

    # ---- shared experts (always-on) ----------------------------------------
    if cfg.n_shared_experts > 0:
        gate = jax.nn.sigmoid(xf @ p["shared_gate"]["w"]).astype(x.dtype)
        y = y + gate * ffn_apply(p["shared"], xf, "swiglu")

    # ---- switch-style load-balance loss ------------------------------------
    frac = counts.sum(0).astype(jnp.float32) / jnp.maximum(counts.sum(), 1)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return y.reshape(b, sl, d), aux
