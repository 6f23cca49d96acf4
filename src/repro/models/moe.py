"""Top-k mixture-of-experts with capacity-based dispatch.

Expert-parallel layout: the expert axis of the stacked expert weights is
sharded over the mesh 'data' axis (expert parallelism) and the per-expert
hidden dim over 'model'; token→expert resharding then lowers to all-to-all /
collective traffic, which the roofline pass measures.

Dispatch is scatter-based (Megablocks-style), not one-hot-matmul-based, so it
scales to 384-expert configs: positions-in-expert come from a cumsum over the
(tokens·k, E) assignment one-hot, and tokens are scattered into an (E, C, d)
buffer. Tokens over capacity C are dropped (standard capacity-factor
semantics); the residual path keeps them intact.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dtype_of


def init_moe(key, cfg, d: int) -> dict:
    m = cfg.moe
    dt = dtype_of(cfg)
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, f = m.num_experts, m.d_ff
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(kr, (d, e), jnp.float32) * scale_in).astype(
            jnp.float32
        ),
        "w_gate": (jax.random.normal(kg, (e, d, f), jnp.float32) * scale_in).astype(dt),
        "w_up": (jax.random.normal(ku, (e, d, f), jnp.float32) * scale_in).astype(dt),
        "w_down": (jax.random.normal(kd, (e, f, d), jnp.float32) * scale_out).astype(dt),
    }
    if m.num_shared_experts:
        se = m.num_shared_experts
        p["shared_gate"] = (
            jax.random.normal(ks, (se, d, f), jnp.float32) * scale_in
        ).astype(dt)
        k2, k3 = jax.random.split(ks)
        p["shared_up"] = (
            jax.random.normal(k2, (se, d, f), jnp.float32) * scale_in
        ).astype(dt)
        p["shared_down"] = (
            jax.random.normal(k3, (se, f, d), jnp.float32) * scale_out
        ).astype(dt)
    return p


def capacity(num_tokens: int, cfg) -> int:
    m = cfg.moe
    c = math.ceil(m.experts_per_token * num_tokens / m.num_experts * m.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def apply_moe(params: dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch on ``cfg.moe.impl``: 'gather' (pure pjit, baseline) or
    'alltoall' (shard_map expert parallelism — §Perf). Falls back to gather
    when no mesh is set (CPU tests) or experts don't divide the data axis."""
    from repro.sharding import context as shard_ctx

    if getattr(cfg.moe, "impl", "gather") == "alltoall":
        mesh = shard_ctx.get_mesh()
        if mesh is not None and cfg.moe.num_experts % mesh.shape["data"] == 0:
            shards = 1
            for ax in shard_ctx.batch_axes():
                shards *= mesh.shape[ax]
            # shard_map needs the batch dim to divide the batch mesh axes
            # (fails for decode B=1 or small microbatches on multi-pod)
            if x.shape[0] % shards == 0:
                return apply_moe_alltoall(params, x, cfg, mesh)
    return apply_moe_gather(params, x, cfg)


def apply_moe_gather(params: dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) → (y, aux_loss). Routing in fp32."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = m.experts_per_token
    e = m.num_experts
    cap = capacity(t, cfg)

    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0
    )  # fraction routed per expert
    aux = e * jnp.sum(me * ce) * m.aux_loss_weight

    # ---- dispatch: position of each routed assignment within its expert ----
    flat_idx = idx.reshape(t * k)  # token-major
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)  # (T·k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    pos_in_expert = jnp.take_along_axis(pos, flat_idx[:, None], axis=1)[:, 0]
    keep = pos_in_expert < cap
    dest = jnp.where(keep, flat_idx * cap + pos_in_expert, e * cap)  # drop row

    tok_of = jnp.arange(t * k) // k
    buf = jnp.zeros((e * cap + 1, d), dtype=x.dtype)
    buf = buf.at[dest].set(xf[tok_of], mode="drop")
    hidden_in = buf[: e * cap].reshape(e, cap, d)

    # ---- expert compute (E, C, d) × (E, d, f) ----
    h_gate = jnp.einsum("ecd,edf->ecf", hidden_in, params["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", hidden_in, params["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(e * cap, d)

    # ---- combine ----
    y_routed = out[jnp.clip(dest, 0, e * cap - 1)]
    w = (gate.reshape(t * k) * keep).astype(x.dtype)
    y = jnp.zeros((t, d), dtype=x.dtype).at[tok_of].add(y_routed * w[:, None])

    if m.num_shared_experts:
        hg = jnp.einsum("td,edf->tef", xf, params["shared_gate"])
        hu = jnp.einsum("td,edf->tef", xf, params["shared_up"])
        hs = jax.nn.silu(hg) * hu
        y = y + jnp.einsum("tef,efd->td", hs, params["shared_down"])

    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# shard_map expert-parallel implementation (§Perf): tokens are routed LOCALLY
# per data shard and exchanged with the expert-owning shard via exactly one
# all_to_all each way (plus the transposed pair in backward). Under pure pjit
# the scatter/gather dispatch above lowers to full-activation all-reduces and
# collective-permutes per layer (measured 22.8 TB/device/step on
# kimi-k2 × train_4k); this implementation moves only the routed token
# payloads: tokens·top_k·d bytes per layer.
# --------------------------------------------------------------------------
def _dispatch_positions(ids: jnp.ndarray, n_buckets: int, cap: int):
    """ids (N,) → (keep, dest) packing each id's rows into per-bucket slots
    of ``cap``; dest == n_buckets*cap is the drop row."""
    onehot = jax.nn.one_hot(ids, n_buckets, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos, ids[:, None], axis=1)[:, 0]
    keep = (pos < cap) & (ids >= 0)
    dest = jnp.where(keep, ids * cap + pos, n_buckets * cap)
    return keep, dest


def apply_moe_alltoall(
    params: dict, x: jnp.ndarray, cfg, mesh
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P

    from repro.sharding import context as shard_ctx

    m = cfg.moe
    b, s, d = x.shape
    bx = shard_ctx.batch_axes()  # ("data",) or ("pod", "data")
    dsize = mesh.shape["data"]
    e_local = m.num_experts // dsize
    k = m.experts_per_token

    route_groups = m.route_groups if 0 < m.route_groups < dsize else 0

    def local_fn(router, w_gate, w_up, w_down, shared, xl):
        # xl: (b_l, s, d); w_gate/w_up: (E_l, d, f_l); w_down: (E_l, f_l, d)
        bl = xl.shape[0]
        tl = bl * s
        xf = xl.reshape(tl, d)
        logits = xf.astype(jnp.float32) @ router  # (T_l, E) — router replicated
        probs = jax.nn.softmax(logits, axis=-1)
        if route_groups:
            # node-limited routing (DeepSeek-V3 / K2): only experts on the
            # token's top-G data shards are eligible.
            gscore = jnp.max(probs.reshape(tl, dsize, e_local), axis=-1)
            _, gsel = jax.lax.top_k(gscore, route_groups)  # (T_l, G)
            allowed = jnp.zeros((tl, dsize), bool).at[
                jnp.arange(tl)[:, None], gsel
            ].set(True)
            probs = jnp.where(
                jnp.repeat(allowed, e_local, axis=1), probs, 0.0
            )
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        # load-balance aux, averaged over the batch axes
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jnp.sum(jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=1),
            axis=0,
        )
        aux = m.num_experts * jnp.sum(me * ce) * m.aux_loss_weight
        for ax in bx:
            aux = jax.lax.pmean(aux, ax)

        if shared is not None:
            sg, su, sd = shared
            hsg = jnp.einsum("td,edf->tef", xf, sg)
            hsu = jnp.einsum("td,edf->tef", xf, su)
            y_shared = jnp.einsum(
                "tef,efd->td", jax.nn.silu(hsg) * hsu, sd
            )  # partial over f_l

        if route_groups:
            # ---- deduplicated dispatch: ONE send per (token, group) --------
            # gates for the token's experts, laid out per (group, local expert)
            gmat = jnp.zeros((tl, m.num_experts), jnp.float32)
            gmat = gmat.at[jnp.arange(tl)[:, None], idx].set(gate)
            gm = jnp.take_along_axis(
                gmat.reshape(tl, dsize, e_local), gsel[..., None], axis=1
            ).reshape(tl * route_groups, e_local)  # (T_l·G, E_l)
            ids1 = gsel.reshape(tl * route_groups)
            tok_of1 = jnp.arange(tl * route_groups) // route_groups
            cap1 = max(8, -(-int(tl * route_groups / dsize * m.capacity_factor) // 8) * 8)
            keep1, dest1 = _dispatch_positions(ids1, dsize, cap1)
            payload = jnp.concatenate([xf[tok_of1], gm.astype(xf.dtype)], axis=1)
            buf = jnp.zeros((dsize * cap1 + 1, d + e_local), xf.dtype)
            buf = buf.at[dest1].set(jnp.where(keep1[:, None], payload, 0), mode="drop")
            send = buf[: dsize * cap1].reshape(dsize, cap1, d + e_local)
            recv = jax.lax.all_to_all(send, "data", split_axis=0, concat_axis=0)

            rx = recv.reshape(dsize * cap1, d + e_local)
            x_r, g_r = rx[:, :d], rx[:, d:].astype(jnp.float32)  # (T2, E_l)
            t2 = dsize * cap1
            # (recv slot, local expert) pairs with nonzero gate
            ids2 = jnp.where(
                g_r > 0, jnp.arange(e_local)[None, :], -1
            ).reshape(t2 * e_local)
            pair_tok = jnp.arange(t2 * e_local) // e_local
            cap2 = max(
                8,
                -(-int(t2 * min(k, e_local) / (route_groups * e_local)
                       * m.capacity_factor) // 8) * 8,
            )
            keep2, dest2 = _dispatch_positions(ids2, e_local, cap2)
            buf2 = jnp.zeros((e_local * cap2 + 1, d), x_r.dtype)
            buf2 = buf2.at[dest2].set(
                jnp.where(keep2[:, None], x_r[pair_tok], 0), mode="drop"
            )
            hidden = buf2[: e_local * cap2].reshape(e_local, cap2, d)
            hg = jnp.einsum("ecd,edf->ecf", hidden, w_gate)
            hu = jnp.einsum("ecd,edf->ecf", hidden, w_up)
            h = jax.nn.silu(hg) * hu
            # NOTE: the model-axis reduction of the f_l partial sums is
            # DELAYED to the very end (§Perf kimi v6): gather/scale/scatter/
            # all_to_all are all linear, so the psum commutes — reducing the
            # (T_l, d) token outputs instead of the (E_l, cap2, d) expert
            # buffer cuts the psum payload ~26×.
            out_flat = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(
                e_local * cap2, d
            )  # partial over f_l
            y_pairs = out_flat[jnp.clip(dest2, 0, e_local * cap2 - 1)]
            wts = (g_r.reshape(-1) * keep2).astype(x_r.dtype)
            y_slot = jnp.zeros((t2, d), x_r.dtype).at[pair_tok].add(
                y_pairs * wts[:, None]
            )
            y_ret = jax.lax.all_to_all(
                y_slot.reshape(dsize, cap1, d), "data", split_axis=0, concat_axis=0
            ).reshape(dsize * cap1, d)
            y_routed = y_ret[jnp.clip(dest1, 0, dsize * cap1 - 1)] * keep1[:, None]
            y = jnp.zeros((tl, d), xl.dtype).at[tok_of1].add(y_routed.astype(xl.dtype))
        else:
            # ---- stage 1: one send per (token, expert), exchange ------------
            flat_idx = idx.reshape(tl * k)
            group = flat_idx // e_local           # destination data shard
            e_loc = flat_idx % e_local            # expert id on that shard
            tok_of = jnp.arange(tl * k) // k
            cap1 = max(8, -(-int(tl * k / dsize * m.capacity_factor) // 8) * 8)
            keep1, dest1 = _dispatch_positions(group, dsize, cap1)

            payload = jnp.concatenate(
                [xf[tok_of], (e_loc + 1).astype(xf.dtype)[:, None]], axis=1
            )  # channel d carries the local-expert id (+1; 0 = pad)
            buf = jnp.zeros((dsize * cap1 + 1, d + 1), xf.dtype)
            buf = buf.at[dest1].set(jnp.where(keep1[:, None], payload, 0), mode="drop")
            send = buf[: dsize * cap1].reshape(dsize, cap1, d + 1)
            recv = jax.lax.all_to_all(send, "data", split_axis=0, concat_axis=0)

            # ---- stage 2: local expert compute ------------------------------
            rx = recv.reshape(dsize * cap1, d + 1)
            x_r = rx[:, :d]
            e_r = jnp.round(rx[:, d].astype(jnp.float32)).astype(jnp.int32) - 1
            t2 = dsize * cap1
            cap2 = max(8, -(-int(t2 / e_local * m.capacity_factor) // 8) * 8)
            keep2, dest2 = _dispatch_positions(e_r, e_local, cap2)
            buf2 = jnp.zeros((e_local * cap2 + 1, d), x_r.dtype)
            buf2 = buf2.at[dest2].set(jnp.where(keep2[:, None], x_r, 0), mode="drop")
            hidden = buf2[: e_local * cap2].reshape(e_local, cap2, d)

            hg = jnp.einsum("ecd,edf->ecf", hidden, w_gate)
            hu = jnp.einsum("ecd,edf->ecf", hidden, w_up)
            h = jax.nn.silu(hg) * hu
            # f_l partial sums carried through the (linear) combine; reduced
            # once on the (T_l, d) outputs at the end — see grouped branch.
            out_flat = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(
                e_local * cap2, d
            )

            y_r = out_flat[jnp.clip(dest2, 0, e_local * cap2 - 1)] * keep2[:, None]
            y_back = y_r.reshape(dsize, cap1, d)
            y_ret = jax.lax.all_to_all(y_back, "data", split_axis=0, concat_axis=0)
            y_flat = y_ret.reshape(dsize * cap1, d)
            y_routed = y_flat[jnp.clip(dest1, 0, dsize * cap1 - 1)]
            w = (gate.reshape(tl * k) * keep1).astype(xl.dtype)
            y = jnp.zeros((tl, d), xl.dtype).at[tok_of].add(y_routed * w[:, None])
        if shared is not None:
            y = y + y_shared.astype(y.dtype)  # also partial over f_l
        y = jax.lax.psum(y, "model")  # single fused model-axis reduction
        return y.reshape(bl, s, d), aux

    batch_spec = P(bx if len(bx) > 1 else bx[0], None, None)
    shared = ()
    shared_spec = ()
    if m.num_shared_experts:
        shared = (params["shared_gate"], params["shared_up"], params["shared_down"])
        shared_spec = (
            P(None, None, "model"),
            P(None, None, "model"),
            P(None, "model", None),
        )

    def wrapper(router, w_gate, w_up, w_down, shared_tuple, xl):
        return local_fn(
            router, w_gate, w_up, w_down, shared_tuple if shared_tuple else None, xl
        )

    fn = jax.shard_map(
        wrapper,
        mesh=mesh,
        in_specs=(
            P(),                          # router (replicated fp32)
            P("data", None, "model"),     # w_gate
            P("data", None, "model"),     # w_up
            P("data", "model", None),     # w_down
            shared_spec,
            batch_spec,                   # x
        ),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )
    return fn(
        params["router"], params["w_gate"], params["w_up"], params["w_down"],
        shared, x,
    )
