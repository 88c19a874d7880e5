"""Mixture-of-Experts FFN: dropless serving over a share of the experts,
and top-k capacity routing + explicit EP all-to-all for training.

Serving (``token_mask`` given, every serving mode) is DROPLESS
(`_moe_dropless`): the router scores all ``n_experts``, and the layer
computes the part of the result that the experts held here
(``ArchConfig.experts_held``, a contiguous range) give, for exactly the
(token, pick) pairs routed to them.  The pairs are sorted by expert and
the held experts run as grouped matmuls over the group sizes (megablox
``gmm``, which reads each layer's weights in place out of the layer
stack), so the work follows the picks, no token is dropped and none can
displace another: a token's output does not depend on its batch-mates.
Picks of experts held elsewhere add nothing here (expert parallelism's
exchange is not part of a one-chip step).  On a mesh that shards the
experts, each shard computes its own and one all-reduce adds them.

Training routing is GShard-style top-k with a fixed per-expert capacity, but
*without* the O(tokens x experts x capacity) one-hot dispatch tensors:
assignments are ranked within their expert by a stable sort, giving each a
(expert, capacity-slot) coordinate.

Training's data movement runs under shard_map (`_moe_shardmap`): experts
are sharded over the 'model' axis and each expert's capacity rows are
striped over ('pod','data'), so a token's coordinate names a unique
destination device. Each device buckets its assignments by destination,
performs ONE fused all-to-all over the whole mesh (payload + routing
metadata), computes its local experts, and reverses the all-to-all to
combine — the canonical expert-parallel schedule, with compute and comm
both 1/n_devices. (Letting XLA's SPMD partitioner derive this from
scatter sharding constraints instead produced replicated multi-GB
scatter expansions — see EXPERIMENTS.md §Perf.)

On a single device (tests/examples) the same math runs as the pure-jnp
scatter path (`_moe_dense_path`), which doubles as the shard_map oracle.
Both training paths need every expert held here.

Sub-byte quantization (the paper's technique) pays most here: expert banks
dominate parameter bytes while each token touches only top-k of them, so
packed int4/int2 expert weights cut the dominant HBM term (§Perf).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.core.quant import fake_quant
from repro.distributed.sharding import (current_mesh, lshard, make_spec,
                                       mesh_axes_for)
from repro.models.common import ParamSpec, dense


def moe_specs(cfg) -> dict:
    lo, hi = cfg.held_experts()
    d, e, f = cfg.d_model, hi - lo, cfg.d_ff_expert
    specs = {
        "router": ParamSpec((d, cfg.n_experts), ("embed", None), scale=0.02),
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


def _quant_experts(buf, wg, wu, wd, quant):
    """The paper's quantization emulation of expert weights and inputs."""
    if quant is not None and quant.quantized:
        wg = fake_quant(wg, quant.w_bits, 1)
        wu = fake_quant(wu, quant.w_bits, 1)
        wd = fake_quant(wd, quant.w_bits, 1)
        if quant.mode in ("int", "qat"):
            buf = fake_quant(buf, quant.a_bits, -1)
    return buf, wg, wu, wd


def _expert_swiglu(buf, wg, wu, wd, quant, dtype):
    """Batched per-expert SwiGLU with the paper's quantization emulation."""
    buf, wg, wu, wd = _quant_experts(buf, wg, wu, wd, quant)
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


BANKS = ("w_gate", "w_up", "w_down")
ROW_TILE = 128      # the grouped matmul's row block; dropless rows round to it


def _block(dim: int, cap: int = 1024) -> int:
    """The grouped matmul's block along a weight dim: the whole dim up to
    1.5 x ``cap``, else the largest multiple of 128 at most ``cap`` that
    divides it."""
    if dim <= cap + cap // 2:
        return dim
    return next((b for b in range(cap, 127, -128) if dim % b == 0), dim)


def _grouped(x, w, sizes, base):
    """Rows of ``x`` sorted by group, each group g times ``w[base + g]``,
    f32 (megablox ``gmm``; compiled for the TPU, interpreted for any
    other platform).  ``w`` is (groups, K, N) and may hold more groups
    than ``sizes`` (every layer's experts, ``base`` picking one layer's):
    the kernel reads only the blocks of groups with rows, so weights are
    read in place, once for each row tile of their group.  Rows past
    sum(sizes) are left unwritten."""
    _, kd, nd = w.shape
    run = functools.partial(
        gmm, preferred_element_type=jnp.float32,
        tiling=(ROW_TILE, _block(kd), _block(nd)),
        group_offset=jnp.asarray(-base, jnp.int32))
    return jax.lax.platform_dependent(
        x, w, sizes, tpu=functools.partial(run, interpret=False),
        default=functools.partial(run, interpret=True))


def _dropless_local(xf, expert_idx, gate_vals, valid, banks, layer, lo, hi,
                    cfg):
    """The experts [lo, hi) of ``banks``' layer ``layer`` (banks stacked
    (L, hi - lo, ...) over the layers; ``layer`` None: one layer's
    (hi - lo, ...)) for the (token, pick) pairs routed to them.  Returns
    (y (T, D), sizes (hi - lo,) picks each expert got)."""
    t, d = xf.shape
    k = cfg.top_k
    a = t * k
    m = -(-a // ROW_TILE) * ROW_TILE
    e_flat = expert_idx.reshape(a)
    held = jnp.repeat(valid, k) & (e_flat >= lo) & (e_flat < hi)
    group = jnp.where(held, e_flat - lo, hi - lo).astype(jnp.int32)
    group = jnp.pad(group, (0, m - a), constant_values=hi - lo)
    order = jnp.argsort(group, stable=True)            # pair at each row
    sizes = jnp.zeros((hi - lo,), jnp.int32).at[group].add(1, mode="drop")
    xs = xf[jnp.minimum(order // k, t - 1)]
    ws, base = banks, 0
    quant = cfg.quant is not None and cfg.quant.quantized
    if layer is not None and quant:                # emulate on this layer
        ws = tuple(w[layer] for w in ws)
    elif layer is not None:                        # read the stack in place
        ws = tuple(w.reshape((-1,) + w.shape[2:]) for w in ws)
        base = layer * (hi - lo)
    xs, wg, wu, wd = _quant_experts(xs, *ws, cfg.quant)
    hm = jax.nn.silu(_grouped(xs, wg, sizes, base)) * \
        _grouped(xs, wu, sizes, base)
    if cfg.quant is not None and cfg.quant.mode in ("int", "qat"):
        hm = fake_quant(hm, cfg.quant.a_bits, -1)
    ys = _grouped(hm.astype(xf.dtype), wd, sizes, base)
    ys = jnp.where((jnp.arange(m) < jnp.sum(sizes))[:, None], ys, 0)
    row_of = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))                 # row of each pair
    y = ys[row_of[:a]].reshape(t, k, d) * gate_vals[..., None]
    return jnp.sum(y, axis=1).astype(xf.dtype), sizes


def _moe_dropless(banks, layer, xf, expert_idx, gate_vals, valid, cfg):
    """Dropless expert layer over the experts held here.

    xf: (T, D); expert_idx, gate_vals: (T, k) routed over all n_experts;
    valid: (T,) bool, False for padding (no pick is counted or computed);
    banks: the expert weights (``BANKS``), stacked over the layers when
    ``layer`` is given.  The T*k (token, pick) pairs are sorted by held
    expert, picks of experts not held (and padding) last; the held
    experts run as three grouped matmuls over the group sizes on a
    static T*k rows rounded up to ROW_TILE.  Each token's output is the
    gate-weighted sum of its own picks' rows, read back in pick order,
    so it depends on nothing else in the batch.

    On a mesh (the kernel cannot be partitioned for it) the layer runs
    under shard_map: where the mesh shards the 'expert' axis, each shard
    computes the held experts it holds for every token of its batch
    shard and the shards' sums are added (one all-reduce of (T, D)); no
    expert weight moves.

    Returns (y (T, D), picks f32 (3,): [picks routed to held experts, all
    picks, held experts with any pick])."""
    lo, hi = cfg.held_experts()
    k = cfg.top_k
    mesh, ex = mesh_axes_for("expert")
    if mesh is None or mesh.size == 1:
        y, sizes = _dropless_local(xf, expert_idx, gate_vals, valid, banks,
                                   layer, lo, hi, cfg)
        return y, jnp.stack([jnp.sum(sizes), k * jnp.sum(valid),
                             jnp.sum(sizes > 0)]).astype(jnp.float32)
    n_ex = math.prod(mesh.shape[a] for a in ex)
    if (hi - lo) % n_ex:                # the banks are replicated then
        ex, n_ex = (), 1
    _, bt = mesh_axes_for("batch")
    dp = tuple(a for a in bt if a not in ex)
    if xf.shape[0] % math.prod(mesh.shape[a] for a in dp):
        dp = ()
    e_loc = (hi - lo) // n_ex
    lead = (None,) if layer is not None else ()

    def psum(x, axes):
        return jax.lax.psum(x, axes) if axes else x

    def local(x_l, idx_l, gate_l, valid_l, *banks_l):
        i = 0
        for ax in ex:
            i = i * mesh.shape[ax] + jax.lax.axis_index(ax)
        lo_l = lo + i * e_loc
        # this shard's experts [lo_l, lo_l + e_loc), renumbered to start
        # at lo so that the local range has a static width
        y, sizes = _dropless_local(x_l, idx_l - lo_l + lo, gate_l, valid_l,
                                   banks_l, layer, lo, lo + e_loc, cfg)
        picks = jnp.stack([psum(jnp.sum(sizes), dp + ex),
                           k * psum(jnp.sum(valid_l), dp),
                           psum(jnp.sum(psum(sizes, dp) > 0), ex)])
        return psum(y, ex), picks.astype(jnp.float32)

    tok = P(dp or None)
    bank = P(*lead, ex or None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok, tok, tok, tok) + (bank,) * len(banks),
        out_specs=(tok, P()), check_vma=False)(
            xf, expert_idx, gate_vals, valid, *banks)


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
    """x: (B, S, D) -> (out (B, S, D), aux).

    Serving passes ``token_mask``, a (B, S) bool that is False at padding
    (chunk padding, inactive decode slots): the layer is then dropless
    over the experts held here (`_moe_dropless`), padding is neither
    routed nor counted, and ``aux`` is the f32 (3,) [picks routed to held
    experts, all picks, held experts with any pick].  Serving's layer
    scan hands over the expert banks stacked over all its layers, with
    this layer's index as ``p["layer"]`` (``model._apply_stage``), so
    the kernel reads them where they lie.  Without ``token_mask``
    (training) ``aux`` is the scalar load-balance loss and routing has a
    fixed per-expert capacity.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = lshard(x.reshape(t, d), "batch", None)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    logits = lshard(logits, "batch", None)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)            # (T, k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    if token_mask is not None:
        y, aux = _moe_dropless(tuple(p[n] for n in BANKS), p.get("layer"),
                               xf, expert_idx, gate_vals,
                               token_mask.reshape(t), cfg)
        return _add_shared(p, xf, y, cfg).reshape(b, s, d), aux
    if cfg.held_experts() != (0, e):
        raise ValueError(f"{cfg.name}: training needs every expert held "
                         f"here, not {cfg.experts_held}")

    # load-balancing auxiliary loss (Switch-style).
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce)

    cap = _capacity(t, e, k, cfg.capacity_factor)
    a = t * k
    layout = _ep_layout(cfg, b, s, cap, current_mesh())
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
    return _add_shared(p, xf, y, cfg).reshape(b, s, d), aux


def _add_shared(p, xf, y, cfg):
    """y plus the shared experts' SwiGLU of xf, where the layer has them."""
    if "shared" not in p:
        return y
    sh = p["shared"]
    gs = dense(xf, sh["w_gate"], cfg.quant)
    us = dense(xf, sh["w_up"], cfg.quant)
    hs = jax.nn.silu(gs.astype(jnp.float32)).astype(xf.dtype) * us
    return y + dense(hs, sh["w_down"], cfg.quant)
