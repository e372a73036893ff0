"""Expert-parallel MoE block (shard_map + all_to_all).

Production layout:
  * expert weights (E_eff, d, ff_s): experts over the "model" axis (EP),
    d additionally ZeRO-sharded over ("pod","data") — all-gathered per layer
    at use (FSDP-style; the gather is the collective the roofline sees);
  * tokens: capacity-factor dispatch (Switch/GShard style) computed locally,
    then ONE all_to_all over the model axis sends each expert-shard its
    tokens; the reverse all_to_all returns them. No one-hot einsum dispatch —
    routing is gather/scatter, so HLO FLOPs stay honest.

``expert_shards`` (grok: 2) splits every expert's d_ff so E*shards maps 1:1
onto the model axis when E < axis size; a token visits all shards of its
routed expert and partial outputs are summed — mathematically exact, at the
cost of duplicating that token's dispatch bytes.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.sharding import ShardingRules, _resolve_axes


def _capacity(tokens_local: int, cfg: ModelConfig) -> int:
    c = int(np.ceil(tokens_local * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)


def moe_block_decode_gathered(
    cfg: ModelConfig,
    mesh: Mesh,
    rules: ShardingRules,
    x: jax.Array,  # (b, 1, d) global
    wr: jax.Array,
    wi: jax.Array,  # (E_eff, d, ff_s) — E_eff sharded over ALL mesh axes
    wg: jax.Array,
    wo: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Decode-optimal MoE (§Perf cell B): weights stay fully resident
    (E*ff_shards spread across every device); the tiny token batch is
    all-gathered, every device computes its expert-shard's contribution for
    the tokens routed to it, and outputs are psum'd. Bytes per layer =
    O(batch * d), independent of expert size — vs O(E_local * d * ff) for
    weight gathering."""
    tab = rules.table()
    ep = _resolve_axes(tab["experts"], mesh)
    ep_axes = (ep,) if isinstance(ep, str) else tuple(ep or ())
    batch_ax = _resolve_axes(tab["batch"], mesh)
    b_axes = (batch_ax,) if isinstance(batch_ax, str) else tuple(batch_ax or ())
    all_axes = tuple(mesh.axis_names)
    e, s_shards, e_eff = cfg.n_experts, cfg.expert_shards, cfg.n_experts_eff
    n_dev = int(np.prod([mesh.shape[a] for a in all_axes]))
    assert e_eff % n_dev == 0 or n_dev % e_eff == 0, (e_eff, n_dev)

    def local_fn(x_loc, wr_loc, wi_loc, wg_loc, wo_loc):
        b_loc, _, d = x_loc.shape
        xt = x_loc[:, 0, :]  # (b_loc, d)
        # gather the whole (tiny) token batch onto every device
        x_all = jax.lax.all_gather(xt, b_axes, axis=0, tiled=True)  # (B, d)
        logits = x_all.astype(jnp.float32) @ wr_loc.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, tope = jax.lax.top_k(probs, cfg.top_k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
        # my expert-shard's weight: how many tokens route to my real expert
        e_loc = wi_loc.shape[0]  # expert-shards resident on this device
        my_first = jax.lax.axis_index(all_axes) * e_loc if e_loc else 0
        y_partial = jnp.zeros((x_all.shape[0], d), jnp.float32)
        for j in range(e_loc):
            shard_id = my_first + j
            real_e = shard_id // s_shards
            h = jnp.einsum("td,df->tf", x_all, wi_loc[j]) * jax.nn.silu(
                jnp.einsum("td,df->tf", x_all, wg_loc[j])
            )
            y_e = jnp.einsum("tf,fd->td", h, wo_loc[j]).astype(jnp.float32)
            w_tok = jnp.sum(
                jnp.where(tope == real_e, topv, 0.0), axis=-1
            )  # (B,)
            y_partial = y_partial + y_e * w_tok[:, None]
        y_all = jax.lax.psum(y_partial, all_axes)  # (B, d)
        # slice back this device's batch shard
        bi = jax.lax.axis_index(b_axes) if b_axes else 0
        y_loc = jax.lax.dynamic_slice_in_dim(y_all, bi * b_loc, b_loc, axis=0)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(tope[:, 0], e, dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)
        return y_loc[:, None, :].astype(x_loc.dtype), aux

    ep_spec = ep_axes if len(ep_axes) != 1 else ep_axes[0]
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(batch_ax, None, None), P(None, None),
                  P(ep_spec, None, None), P(ep_spec, None, None),
                  P(ep_spec, None, None)),
        out_specs=(P(batch_ax, None, None), P()),
        check_vma=False,
    )
    return fn(x, wr, wi, wg, wo)


def moe_block(
    cfg: ModelConfig,
    mesh: Mesh,
    rules: ShardingRules,
    x: jax.Array,  # (b, s, d) global
    wr: jax.Array,  # (d, E) router
    wi: jax.Array,  # (E_eff, d, ff_s)
    wg: jax.Array,  # (E_eff, d, ff_s)
    wo: jax.Array,  # (E_eff, ff_s, d)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss). y sharded like x."""
    if x.shape[1] == 1 and rules.table().get("moe_decode_gathered"):
        return moe_block_decode_gathered(cfg, mesh, rules, x, wr, wi, wg, wo)
    tab = rules.table()
    model_ax = _resolve_axes(tab["experts"], mesh)
    batch_ax = _resolve_axes(tab["batch"], mesh)
    seq_ax = _resolve_axes(tab["seq"], mesh)
    fsdp_ax = _resolve_axes(tab["expert_fsdp"], mesh)
    # the expert axis may be a tuple (EP-everywhere serving: experts over
    # model x data, zero weight movement)
    ep_axes = (
        (model_ax,) if isinstance(model_ax, str)
        else tuple(model_ax) if model_ax is not None else ()
    )
    ma = int(np.prod([mesh.shape[a] for a in ep_axes])) if ep_axes else 1
    # fsdp axes overlapping the EP axes are disabled (weights fully resident)
    if fsdp_ax is not None:
        fs = (fsdp_ax,) if isinstance(fsdp_ax, str) else tuple(fsdp_ax)
        fs = tuple(a for a in fs if a not in ep_axes)
        fsdp_ax = fs[0] if len(fs) == 1 else (fs if fs else None)

    def _axsize(ax):
        if ax is None:
            return 1
        return mesh.shape[ax] if isinstance(ax, str) else int(
            np.prod([mesh.shape[a] for a in ax]))

    # divisibility guards (decode: seq == 1; tiny smoke batches)
    if x.shape[1] % _axsize(seq_ax) != 0:
        seq_ax = None
    if x.shape[0] % _axsize(batch_ax) != 0:
        batch_ax = None

    e, s_shards = cfg.n_experts, cfg.expert_shards
    e_eff = cfg.n_experts_eff
    assert e_eff % max(ma, 1) == 0, (e_eff, ma)

    x_spec = P(batch_ax, seq_ax, None)
    ep_spec = ep_axes if len(ep_axes) != 1 else ep_axes[0]
    w_fsdp_in = P(ep_spec if ep_axes else None, fsdp_ax, None)
    w_fsdp_out = P(ep_spec if ep_axes else None, None, fsdp_ax)

    def local_fn(x_loc, wr_loc, wi_loc, wg_loc, wo_loc):
        b_loc, s_loc, d = x_loc.shape
        t = b_loc * s_loc
        xt = x_loc.reshape(t, d)
        cap = _capacity(t, cfg)

        # ---- routing (local tokens) ------------------------------------
        logits = (xt.astype(jnp.float32) @ wr_loc.astype(jnp.float32))  # (t, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, tope = jax.lax.top_k(probs, cfg.top_k)  # (t, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

        # load-balance aux loss (Switch): E * sum_e f_e * p_e, globally.
        me = jnp.mean(probs, axis=0)  # (E,)
        ce = jnp.mean(
            (jax.nn.one_hot(tope[:, 0], e, dtype=jnp.float32)), axis=0
        )
        aux = e * jnp.sum(me * ce)
        # replicate across the whole mesh (data axes average token stats;
        # the model axis holds different seq shards, so include it too).
        aux_axes = tuple(
            a
            for ax in (batch_ax, seq_ax)
            if ax is not None
            for a in ((ax,) if isinstance(ax, str) else ax)
        )
        if aux_axes:
            aux = jax.lax.pmean(aux, aux_axes)

        # ---- capacity-based slotting ------------------------------------
        flat_e = tope.reshape(-1)  # (t*k,) token-major, rank-minor
        onehot = (flat_e[:, None] == jnp.arange(e)[None, :]).astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based position
        pos = jnp.sum(pos, axis=-1) - 1  # (t*k,)
        keep = pos < cap
        slot = jnp.where(keep, flat_e * cap + pos, e * cap)  # overflow row

        # ---- dispatch ----------------------------------------------------
        tok_ids = jnp.repeat(jnp.arange(t), cfg.top_k)
        xk = xt[tok_ids]  # (t*k, d)
        buf = jnp.zeros((e * cap + 1, d), dtype=x_loc.dtype).at[slot].add(xk)
        buf = buf[:-1].reshape(e, cap, d)
        if s_shards > 1:
            buf = jnp.repeat(buf, s_shards, axis=0)  # (E_eff, cap, d)

        # ---- EP all_to_all (expert axes) ----------------------------------
        if ma > 1:
            recv = jax.lax.all_to_all(
                buf, ep_axes, split_axis=0, concat_axis=1, tiled=True
            )  # (E_loc, cap*ma, d)
        else:
            recv = buf

        # ---- expert compute (weights FSDP all-gathered over d) -----------
        if fsdp_ax is not None:
            gather_axes = (fsdp_ax,) if isinstance(fsdp_ax, str) else fsdp_ax
            wi_full = jax.lax.all_gather(wi_loc, gather_axes, axis=1, tiled=True)
            wg_full = jax.lax.all_gather(wg_loc, gather_axes, axis=1, tiled=True)
            wo_full = jax.lax.all_gather(wo_loc, gather_axes, axis=2, tiled=True)
        else:
            wi_full, wg_full, wo_full = wi_loc, wg_loc, wo_loc

        h = jnp.einsum("ecd,edf->ecf", recv, wi_full) * jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", recv, wg_full)
        )
        y = jnp.einsum("ecf,efd->ecd", h, wo_full)  # (E_loc, cap*ma, d)

        # ---- reverse all_to_all + combine ---------------------------------
        if ma > 1:
            y = jax.lax.all_to_all(y, ep_axes, split_axis=1, concat_axis=0,
                                   tiled=True)  # (E_eff, cap, d)
        if s_shards > 1:
            y = y.reshape(e, s_shards, cap, d).sum(axis=1)
        y_flat = y.reshape(e * cap, d)
        y_flat = jnp.concatenate(
            [y_flat, jnp.zeros((1, d), dtype=y_flat.dtype)], axis=0
        )
        yk = jnp.where(keep[:, None], y_flat[slot], 0)  # (t*k, d)
        yk = yk * topv.reshape(-1)[:, None].astype(yk.dtype)
        out = jnp.sum(yk.reshape(t, cfg.top_k, d), axis=1)
        return out.reshape(b_loc, s_loc, d).astype(x_loc.dtype), aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_fsdp_in, w_fsdp_in, w_fsdp_out),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, wr, wi, wg, wo)
