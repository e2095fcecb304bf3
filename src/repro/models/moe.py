"""Mixture-of-Experts block: shared expert(s) + routed top-k with sort-based
capacity dispatch (Megablocks-style grouping, dropping on overflow).

Expert weights carry the expert dim first -> sharded over the "model" axis
(**EP**). The dispatch is written with sort + scatter/gather (no (N, E)
one-hot materialization), so the per-device working set stays
O(N·k + E·C·d / ep_degree).

DeepSeek-style aux-loss-free balancing: a non-trainable per-expert bias is
added to the routing scores for *selection only*; the train step nudges it
against the observed load (see repro.train.step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, split_keys
from repro.models.mlp import init_mlp, mlp_block
from repro.parallel.sharding import hint


def init_moe(key, cfg, dtype):
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32, scale=0.02),
        "bias": jnp.zeros((E,), jnp.float32),  # aux-free balancing bias (not a grad param)
        "wg": dense_init(ks[1], (E, d, f), dtype),
        "wu": dense_init(ks[2], (E, d, f), dtype),
        "wd": dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, f * cfg.n_shared_experts, "swiglu", dtype)
    return p


def _route(p, x2d, cfg):
    """x2d (N, d) -> (expert_ids (N,k), weights (N,k), router_probs (N,E))."""
    logits = jnp.einsum("nd,de->ne", x2d.astype(jnp.float32), p["router"])
    if cfg.router_gate == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    sel = scores + p["bias"][None, :]               # bias affects selection only
    _, ids = jax.lax.top_k(sel, cfg.top_k)          # (N, k)
    w = jnp.take_along_axis(scores, ids, axis=-1)   # original scores as weights
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return ids, w, scores


def moe_block(p, x, cfg):
    """x (B,S,d) -> (y (B,S,d), aux dict with load stats).

    With REPRO_MOE_SHARDMAP=1 and an active mesh, dispatch runs inside
    shard_map: each (data, model) device scatters ITS tokens into ITS local
    expert shard's buffer and the outputs combine with one psum over "model"
    — GSPMD-auto otherwise replicates the (E, C, d) dispatch buffers, which
    costs terabytes of all-reduce on deepseek-v3 (§Perf iteration 2)."""
    import os
    if os.environ.get("REPRO_MOE_SHARDMAP") == "1":
        from repro.compat import current_mesh
        env_mesh = current_mesh()
        if env_mesh is not None and "model" in env_mesh.axis_names \
                and cfg.num_experts % env_mesh.shape["model"] == 0:
            return _moe_block_shardmap(p, x, cfg, env_mesh)
    return _moe_block_gspmd(p, x, cfg)


def _dispatch_compute_combine(p_local, x2d, ids, w, cfg, n_local_experts,
                              expert_offset):
    """Local-token x local-expert-shard MoE. Returns partial y (N, d)."""
    N, d = x2d.shape
    k = cfg.top_k
    flat_ids = ids.reshape(N * k) - expert_offset
    flat_w = w.reshape(N * k)
    tok_idx = jnp.repeat(jnp.arange(N), k)
    mine = (flat_ids >= 0) & (flat_ids < n_local_experts)
    lids = jnp.where(mine, flat_ids, 0)
    order = jnp.argsort(jnp.where(mine, lids, n_local_experts))  # mine first
    s_ids = lids[order]
    s_tok = tok_idx[order]
    s_w = flat_w[order]
    s_mine = mine[order]
    start = jnp.searchsorted(s_ids, jnp.arange(n_local_experts), side="left")
    rank = jnp.arange(N * k) - start[s_ids]
    C = int(max(8, (N * k / cfg.num_experts) * cfg.capacity_factor))
    C = -(-C // 8) * 8
    keep = s_mine & (rank < C)
    slot_e = jnp.where(keep, s_ids, 0)
    slot_c = jnp.where(keep, rank, 0)
    xbuf = jnp.zeros((n_local_experts, C, d), x2d.dtype)
    xbuf = xbuf.at[slot_e, slot_c].add(x2d[s_tok] * keep[:, None].astype(x2d.dtype))
    g = jnp.einsum("ecd,edf->ecf", xbuf, p_local["wg"])
    u = jnp.einsum("ecd,edf->ecf", xbuf, p_local["wu"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x2d.dtype) * u
    ybuf = jnp.einsum("ecf,efd->ecd", h, p_local["wd"])
    y_tok = ybuf[slot_e, slot_c] * (s_w * keep)[:, None].astype(x2d.dtype)
    return jnp.zeros((N, d), x2d.dtype).at[s_tok].add(y_tok)


def _moe_block_shardmap(p, x, cfg, mesh):
    """Expert parallelism via shard_map: tokens sharded over ("pod","data"),
    experts over "model"; combine = one psum("model") of the (N_local, d)
    partial outputs."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E = cfg.num_experts
    data_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    ep = mesh.shape["model"]
    assert E % ep == 0
    n_local = E // ep

    def local(x_loc, router, bias, wg, wu, wd, shared):
        # x_loc (B/dp, S, d); wg (E/ep, d, f)
        Bl, Sl, _ = x_loc.shape
        x2d = x_loc.reshape(Bl * Sl, d)
        logits = jnp.einsum("nd,de->ne", x2d.astype(jnp.float32), router)
        scores = (jax.nn.sigmoid(logits) if cfg.router_gate == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        sel = scores + bias[None, :]
        _, ids = jax.lax.top_k(sel, cfg.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        off = jax.lax.axis_index("model") * n_local
        y = _dispatch_compute_combine({"wg": wg, "wu": wu, "wd": wd}, x2d,
                                      ids, w, cfg, n_local, off)
        y = jax.lax.psum(y, "model")
        load = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
        load = jax.lax.pmean(load, ("model",) + data_axes) / (Bl * Sl * cfg.top_k)
        if shared is not None:
            # shared expert: d_ff sharded over model -> partial sums psum'ed
            sg = jnp.einsum("nd,df->nf", x2d, shared["wg"])
            su = jnp.einsum("nd,df->nf", x2d, shared["wu"])
            sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x2d.dtype) * su
            y = y + jax.lax.psum(jnp.einsum("nf,fd->nd", sh, shared["wd"]),
                                 "model")
        return y.reshape(Bl, Sl, d), load

    dp = P(data_axes)
    shared_p = p.get("shared")
    shared_specs = ({"wg": P(None, "model"), "wu": P(None, "model"),
                     "wd": P("model", None)} if shared_p is not None else None)
    y, load = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp[0] if len(data_axes) == 1 else data_axes, None, None),
                  P(None, None), P(None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None), shared_specs),
        out_specs=(P(data_axes if len(data_axes) > 1 else data_axes[0],
                     None, None), P()),
        check_vma=False,
    )(x, p["router"].astype(jnp.float32), p["bias"], p["wg"], p["wu"],
      p["wd"], shared_p)
    aux = {"load": load,
           "router_entropy": jnp.zeros(()),
           "dropped": jnp.zeros(())}
    return y, aux


def _moe_block_gspmd(p, x, cfg):
    B, S, d = x.shape
    N = B * S
    E, k, f = cfg.num_experts, cfg.top_k, cfg.moe_d_ff
    x2d = x.reshape(N, d)
    ids, w, probs = _route(p, x2d, cfg)

    # --- sort-based dispatch -------------------------------------------------
    flat_ids = ids.reshape(N * k)
    flat_w = w.reshape(N * k)
    tok_idx = jnp.repeat(jnp.arange(N), k)
    order = jnp.argsort(flat_ids)                  # stable
    s_ids = flat_ids[order]
    s_tok = tok_idx[order]
    s_w = flat_w[order]
    # rank of each entry within its expert = position - first position of expert
    start = jnp.searchsorted(s_ids, jnp.arange(E), side="left")   # (E,)
    rank = jnp.arange(N * k) - start[s_ids]
    C = int(max(8, (N * k / E) * cfg.capacity_factor))
    C = -(-C // 8) * 8                              # round up to x8
    keep = rank < C
    slot_e = jnp.where(keep, s_ids, 0)
    slot_c = jnp.where(keep, rank, 0)

    xbuf = jnp.zeros((E, C, d), x.dtype)
    gathered = hint(x2d[s_tok] * keep[:, None].astype(x.dtype), "D", None)
    xbuf = hint(xbuf.at[slot_e, slot_c].add(gathered), "M", "D", None)

    # --- grouped expert FFN (E sharded over "model" = EP) --------------------
    g = hint(jnp.einsum("ecd,edf->ecf", xbuf, p["wg"]), "M", "D", None)
    u = hint(jnp.einsum("ecd,edf->ecf", xbuf, p["wu"]), "M", "D", None)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    ybuf = hint(jnp.einsum("ecf,efd->ecd", h, p["wd"]), "M", "D", None)

    # --- combine --------------------------------------------------------------
    y_tok = hint(ybuf[slot_e, slot_c] * (s_w * keep)[:, None].astype(x.dtype),
                 "D", None)
    y2d = hint(jnp.zeros((N, d), x.dtype).at[s_tok].add(y_tok), "D", None)
    y = y2d.reshape(B, S, d)

    if cfg.n_shared_experts:
        y = y + mlp_block(p["shared"], x)

    load = jnp.zeros((E,), jnp.float32).at[flat_ids].add(1.0) / (N * k)
    aux = {
        "load": load,                               # fraction of assignments per expert
        "router_entropy": -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1)),
        "dropped": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y, aux
