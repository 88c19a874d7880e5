"""Mixture-of-Experts FFN: top-k capacity routing + explicit EP all-to-all.

Routing is GShard-style top-k with a fixed per-expert capacity, but
*without* the O(tokens x experts x capacity) one-hot dispatch tensors:
assignments are ranked within their expert by a stable sort, giving each a
(expert, capacity-slot) coordinate.

Data movement runs under shard_map (`_moe_shardmap`): experts are sharded
over the 'model' axis and each expert's capacity rows are striped over
('pod','data'), so a token's coordinate names a unique destination device.
Each device buckets its assignments by destination, performs ONE fused
all-to-all over the whole mesh (payload + routing metadata), computes its
local experts, and reverses the all-to-all to combine — the canonical
expert-parallel schedule, with compute and comm both 1/n_devices.  (Letting
XLA's SPMD partitioner derive this from scatter sharding constraints
instead produced replicated multi-GB scatter expansions — see
EXPERIMENTS.md §Perf.)

On a single device (tests/examples) the same math runs as the pure-jnp
scatter path (`_moe_dense_path`), which doubles as the shard_map oracle.

Sub-byte quantization (the paper's technique) pays most here: expert banks
dominate parameter bytes while each token touches only top-k of them, so
packed int4/int2 expert weights cut the dominant HBM term (§Perf).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quant import fake_quant
from repro.distributed.sharding import current_mesh, lshard, make_spec
from repro.models.common import ParamSpec, dense


def moe_specs(cfg) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    specs = {
        "router": ParamSpec((d, e), ("embed", None), scale=0.02),
        "w_gate": ParamSpec((e, d, f), ("expert", "embed", "ffn"), quantize=True),
        "w_up": ParamSpec((e, d, f), ("expert", "embed", "ffn"), quantize=True),
        "w_down": ParamSpec((e, f, d), ("expert", "ffn", "embed"), quantize=True),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        specs["shared"] = {
            "w_gate": ParamSpec((d, fs), ("embed", "ffn"), quantize=True),
            "w_up": ParamSpec((d, fs), ("embed", "ffn"), quantize=True),
            "w_down": ParamSpec((fs, d), ("ffn", "embed"), quantize=True),
        }
    return specs


def _capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k / n_experts * factor))
    # large capacities align to 512 so the capacity dim shards over
    # ('pod','data'); tiny (test/decode) capacities align to 8.
    if c >= 512:
        return ((c + 511) // 512) * 512
    return max(8, ((c + 7) // 8) * 8)


def _rank_in_group(ids: jax.Array) -> jax.Array:
    """Rank of each element within its equal-id group (stable order)."""
    a = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    seg = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    ranks_sorted = jnp.arange(a, dtype=jnp.int32) - seg.astype(jnp.int32)
    return jnp.zeros((a,), jnp.int32).at[order].set(ranks_sorted)


def _expert_swiglu(buf, wg, wu, wd, quant, dtype):
    """Batched per-expert SwiGLU with the paper's quantization emulation."""
    if quant is not None and quant.quantized:
        wg = fake_quant(wg, quant.w_bits, 1)
        wu = fake_quant(wu, quant.w_bits, 1)
        wd = fake_quant(wd, quant.w_bits, 1)
        if quant.mode in ("int", "qat"):
            buf = fake_quant(buf, quant.a_bits, -1)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    if quant is not None and quant.mode in ("int", "qat"):
        h = fake_quant(h, quant.a_bits, -1)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _moe_dense_path(p, xf, idx_e, idx_c, keep, gate_vals, cap, cfg):
    """Pure-jnp dispatch/combine (single device; oracle for the EP path)."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    a = t * k
    token_of_a = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    buf = jnp.zeros((e, cap, d), xf.dtype).at[idx_e, idx_c].set(
        xf[token_of_a], mode="drop")
    y_e = _expert_swiglu(buf, p["w_gate"], p["w_up"], p["w_down"],
                         cfg.quant, xf.dtype)
    slot = idx_e * cap + idx_c
    y_a = y_e.reshape(e * cap, d)[jnp.minimum(slot, e * cap - 1)]
    y_a = jnp.where(keep[:, None], y_a, 0)
    y_a = y_a * gate_vals.reshape(a, 1).astype(xf.dtype)
    return y_a.reshape(t, k, d).sum(axis=1)


def _moe_shardmap(p, x, expert_idx, gate_vals, cap, cfg, mesh,
                  dp_axes, ep_axes):
    """Expert-parallel dispatch with one explicit all-to-all each way.

    x: (B, S, D); expert_idx/gates: (B, S, k).  Experts sharded over
    ep_axes ('model'), capacity rows striped over dp_axes ('pod','data').

    Capacity slots are assigned HIERARCHICALLY: each device ranks its own
    assignments per expert (a small local sort) and learns its global
    offset from an all-gathered (n_dev, E) count table — a replicated
    global sort over all tokens x top_k was the single largest HBM term in
    the MoE baseline profile (EXPERIMENTS.md §Perf).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    all_axes = tuple(dp_axes) + tuple(ep_axes)
    n_dp = math.prod(mesh.shape[a] for a in dp_axes)
    n_ep = math.prod(mesh.shape[a] for a in ep_axes)
    n_dev = n_dp * n_ep
    e_loc = e // n_ep
    c_loc = cap // n_dp
    t_loc = (b // n_dp) * (s // n_ep)
    a_loc = t_loc * k
    # per-destination send capacity: expected A_loc/n_dev, padded for skew.
    send_cap = max(8, int(math.ceil(
        a_loc / n_dev * 2 * cfg.capacity_factor / 8)) * 8)

    x_spec = P(dp_axes if b % n_dp == 0 else None,
               ep_axes if s % n_ep == 0 else None, None)
    i_spec = P(x_spec[0], x_spec[1], None)
    wio_spec = (make_spec(("expert", "embed", "ffn")),
                make_spec(("expert", "embed", "ffn")),
                make_spec(("expert", "ffn", "embed")))

    def local_fn(x_l, ie_l, gate_l, wg_l, wu_l, wd_l):
        tl = x_l.shape[0] * x_l.shape[1]
        al = tl * k
        xf = x_l.reshape(tl, d)
        ie = ie_l.reshape(al)
        # --- hierarchical global capacity slots -------------------------
        d_lin = 0
        for ax in tuple(dp_axes) + tuple(ep_axes):
            d_lin = d_lin * mesh.shape[ax] + jax.lax.axis_index(ax)
        r_loc = _rank_in_group(ie)                       # local per-expert
        counts = jnp.zeros((e,), jnp.int32).at[ie].add(1)
        counts_all = jax.lax.all_gather(
            counts, tuple(dp_axes) + tuple(ep_axes), axis=0, tiled=False)
        offsets = jnp.cumsum(counts_all, axis=0) - counts_all  # exclusive
        my_off = offsets[d_lin]                          # (E,)
        g_rank = my_off[ie] + r_loc
        kp = g_rank < cap
        ic = jnp.where(kp, g_rank, 0).astype(jnp.int32)
        # destination device of each assignment (row-major (dp, ep) order,
        # matching all_to_all's linearization of the combined axes).
        dest = jnp.where(kp, (ic // c_loc) * n_ep + ie // e_loc, n_dev)
        rank = _rank_in_group(dest)
        kp2 = kp & (rank < send_cap)
        dd = jnp.where(kp2, dest, n_dev).astype(jnp.int32)     # drop -> OOB
        rr = jnp.where(kp2, rank, 0).astype(jnp.int32)
        token_of_a = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), k)
        send_x = jnp.zeros((n_dev, send_cap, d), x_l.dtype
                           ).at[dd, rr].set(xf[token_of_a], mode="drop")
        # metadata: local expert, local capacity row (+1 so 0 = empty slot).
        meta = jnp.zeros((n_dev, send_cap, 2), jnp.int32)
        meta = meta.at[dd, rr, 0].set(ie % e_loc + 1, mode="drop")
        meta = meta.at[dd, rr, 1].set(ic % c_loc, mode="drop")

        recv_x = jax.lax.all_to_all(send_x, all_axes, 0, 0, tiled=False)
        recv_m = jax.lax.all_to_all(meta, all_axes, 0, 0, tiled=False)
        recv_x = recv_x.reshape(n_dev * send_cap, d)
        me_ = recv_m[..., 0].reshape(n_dev * send_cap)
        mc_ = recv_m[..., 1].reshape(n_dev * send_cap)
        # empty slots carry expert id 0 -> map to OOB e_loc for drop.
        buf = jnp.zeros((e_loc, c_loc, d), x_l.dtype).at[
            jnp.where(me_ > 0, me_ - 1, e_loc), mc_].set(recv_x, mode="drop")

        wg = jax.lax.all_gather(wg_l, dp_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu_l, dp_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd_l, dp_axes, axis=2, tiled=True)
        y_buf = _expert_swiglu(buf, wg, wu, wd, cfg.quant, x_l.dtype)

        back = y_buf[jnp.where(me_ > 0, me_ - 1, 0), mc_]
        back = jnp.where((me_ > 0)[:, None], back, 0)
        back = back.reshape(n_dev, send_cap, d)
        ret = jax.lax.all_to_all(back, all_axes, 0, 0, tiled=False)
        y_a = ret[jnp.minimum(dd, n_dev - 1), rr]
        y_a = jnp.where(kp2[:, None], y_a, 0)
        y_a = y_a * gate_l.reshape(al, 1).astype(x_l.dtype)
        y = y_a.reshape(tl, k, d).sum(axis=1)
        return y.reshape(x_l.shape)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, i_spec, i_spec) + wio_spec,
        out_specs=x_spec, check_vma=False)(
            x, expert_idx, gate_vals,
            p["w_gate"], p["w_up"], p["w_down"])


def _ep_layout(cfg, b, s, cap, mesh):
    """(dp_axes, ep_axes) if the EP shard_map layout is legal, else None."""
    if mesh is None:
        return None
    spec = make_spec((None, "seq"))
    ep = spec[1] if len(spec) > 1 else None
    bspec = make_spec(("batch",))
    dp = bspec[0] if len(bspec) else None
    if ep is None or dp is None:
        return None
    ep_axes = (ep,) if isinstance(ep, str) else tuple(ep)
    dp_axes = (dp,) if isinstance(dp, str) else tuple(dp)
    n_ep = math.prod(mesh.shape[a] for a in ep_axes)
    n_dp = math.prod(mesh.shape[a] for a in dp_axes)
    ok = (b % n_dp == 0 and s % n_ep == 0 and cfg.n_experts % n_ep == 0
          and cap % n_dp == 0)
    return (dp_axes, ep_axes) if ok else None


def moe_ffn(p: dict, x: jax.Array, cfg,
            token_mask: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    ``token_mask``: optional (B, S) bool — False positions (chunked-prefill
    padding) are excluded from routing entirely: their expert index is the
    OOB sentinel so they consume NO expert capacity (they must never
    displace a valid token's slot), and their gates are zeroed.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = lshard(x.reshape(t, d), "batch", None)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    logits = lshard(logits, "batch", None)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)            # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    if token_mask is not None:
        tm = token_mask.reshape(t)
        gate_vals = jnp.where(tm[:, None], gate_vals, 0.0)
        expert_idx = jnp.where(tm[:, None], expert_idx, e)

    # load-balancing auxiliary loss (Switch-style).
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce)

    cap = _capacity(t, e, k, cfg.capacity_factor)
    a = t * k
    # the EP shard_map path has no masked-dispatch support; the dense path
    # is numerically identical, so masked (serving chunk) calls take it.
    layout = None if token_mask is not None else \
        _ep_layout(cfg, b, s, cap, current_mesh())
    if layout is not None:
        # slot assignment happens hierarchically inside the shard_map.
        y = _moe_shardmap(p, x, expert_idx.reshape(b, s, k),
                          gate_vals.reshape(b, s, k), cap, cfg,
                          current_mesh(), *layout)
        y = y.reshape(t, d)
    else:
        e_flat = expert_idx.reshape(a)
        rank = _rank_in_group(e_flat)
        keep = rank < cap
        idx_e = jnp.where(keep, e_flat, e).astype(jnp.int32)   # OOB -> drop
        idx_c = jnp.where(keep, rank, 0).astype(jnp.int32)
        y = _moe_dense_path(p, xf, idx_e, idx_c, keep, gate_vals, cap, cfg)

    if "shared" in p:
        sh = p["shared"]
        gs = dense(xf, sh["w_gate"], cfg.quant)
        us = dense(xf, sh["w_up"], cfg.quant)
        hs = jax.nn.silu(gs.astype(jnp.float32)).astype(x.dtype) * us
        y = y + dense(hs, sh["w_down"], cfg.quant)

    return y.reshape(b, s, d), aux
