"""Model factory: parameter schema -> init / shapes / pspecs, plus the
train_step / prefill_step / serve_step builders used by launch & dry-run.

The schema is the single source of truth: each leaf declares (shape,
logical axes, init). ``init_params`` materializes it, ``param_shapes``
returns ShapeDtypeStructs (dry-run: no allocation), ``param_pspecs`` maps
logical axes through the sharding rules for the given mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.layers import pack_bf16, rmsnorm, softmax_cross_entropy, unpack_bf16
from repro.models.sharding import ShardingRules, constrain, spec_for


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[str, ...]
    init: str = "normal"  # normal | zeros | ones | a_log | dt_bias
    dtype: Optional[str] = None  # override model dtype (e.g. norms in f32)


def _attn_defs(cfg: ModelConfig, lead: Tuple[int, ...], lead_log: Tuple[str, ...]):
    h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, cfg.d_model
    defs = {
        "ln1": ParamDef(lead + (d,), lead_log + ("none",), "ones"),
        "wq": ParamDef(lead + (d, h * hd), lead_log + ("fsdp", "tp")),
        "wk": ParamDef(lead + (d, kv * hd), lead_log + ("fsdp", "tp")),
        "wv": ParamDef(lead + (d, kv * hd), lead_log + ("fsdp", "tp")),
        "wo": ParamDef(lead + (h * hd, d), lead_log + ("tp", "fsdp")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef(lead + (h * hd,), lead_log + ("tp",), "zeros")
        defs["bk"] = ParamDef(lead + (kv * hd,), lead_log + ("tp",), "zeros")
        defs["bv"] = ParamDef(lead + (kv * hd,), lead_log + ("tp",), "zeros")
    return defs


def _mlp_defs(cfg: ModelConfig, lead, lead_log):
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "ln2": ParamDef(lead + (d,), lead_log + ("none",), "ones"),
        "wi": ParamDef(lead + (d, ff), lead_log + ("fsdp", "tp")),
        "wg": ParamDef(lead + (d, ff), lead_log + ("fsdp", "tp")),
        "wo_mlp": ParamDef(lead + (ff, d), lead_log + ("tp", "fsdp")),
    }


def _moe_defs(cfg: ModelConfig, lead, lead_log):
    d, ff = cfg.d_model, cfg.d_ff
    e_eff = cfg.n_experts_eff
    ff_s = ff // cfg.expert_shards
    return {
        "ln2": ParamDef(lead + (d,), lead_log + ("none",), "ones"),
        "router": ParamDef(lead + (d, cfg.n_experts), lead_log + ("none", "none")),
        "moe_wi": ParamDef(
            lead + (e_eff, d, ff_s), lead_log + ("experts", "expert_fsdp", "none")
        ),
        "moe_wg": ParamDef(
            lead + (e_eff, d, ff_s), lead_log + ("experts", "expert_fsdp", "none")
        ),
        "moe_wo": ParamDef(
            lead + (e_eff, ff_s, d), lead_log + ("experts", "none", "expert_fsdp")
        ),
    }


def _ssm_defs(cfg: ModelConfig, lead, lead_log):
    d, din = cfg.d_model, cfg.d_inner
    gn = cfg.ssm_ngroups * cfg.ssm_state
    nh, k = cfg.ssm_nheads, cfg.ssm_conv
    return {
        "ln": ParamDef(lead + (d,), lead_log + ("none",), "ones"),
        "wz": ParamDef(lead + (d, din), lead_log + ("fsdp", "tp")),
        "wx": ParamDef(lead + (d, din), lead_log + ("fsdp", "tp")),
        "wb": ParamDef(lead + (d, gn), lead_log + ("fsdp", "tp")),
        "wc": ParamDef(lead + (d, gn), lead_log + ("fsdp", "tp")),
        "wdt": ParamDef(lead + (d, nh), lead_log + ("fsdp", "tp")),
        "dt_bias": ParamDef(lead + (nh,), lead_log + ("tp",), "dt_bias"),
        "a_log": ParamDef(lead + (nh,), lead_log + ("tp",), "a_log"),
        "d_skip": ParamDef(lead + (nh,), lead_log + ("tp",), "ones"),
        "conv_x": ParamDef(lead + (din, k), lead_log + ("tp", "none")),
        "conv_b": ParamDef(lead + (gn, k), lead_log + ("tp", "none")),
        "conv_c": ParamDef(lead + (gn, k), lead_log + ("tp", "none")),
        "norm_w": ParamDef(lead + (din,), lead_log + ("tp",), "ones"),
        "wo": ParamDef(lead + (din, d), lead_log + ("tp", "fsdp")),
    }


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, vp, l = cfg.d_model, cfg.padded_vocab, cfg.n_layers
    defs: Dict[str, Any] = {
        # embed table is sharded on d (not vocab): token gathers stay fully
        # local (no 1-2 GiB table all-gather) and the scatter-add gradient
        # comes out d-sharded instead of replicated.
        "embed": {"table": ParamDef((vp, d), ("none", "tp"))},
        "lm_head": {"w": ParamDef((d, vp), ("fsdp", "vocab"))},
        "final_norm": ParamDef((d,), ("none",), "ones"),
    }
    lead, lead_log = (l,), ("layers",)
    if cfg.family in ("dense", "audio", "vlm"):
        defs["layers"] = {**_attn_defs(cfg, lead, lead_log), **_mlp_defs(cfg, lead, lead_log)}
    elif cfg.family == "moe":
        defs["layers"] = {**_attn_defs(cfg, lead, lead_log), **_moe_defs(cfg, lead, lead_log)}
    elif cfg.family == "ssm":
        defs["layers"] = _ssm_defs(cfg, lead, lead_log)
    elif cfg.family == "hybrid":
        n_sb = cfg.n_layers // cfg.hybrid_period
        defs["layers"] = _ssm_defs(cfg, (n_sb, cfg.hybrid_period), ("layers", "layers"))
        defs["shared"] = {
            **_attn_defs(cfg, (), ()),
            **_mlp_defs(cfg, (), ()),
        }
    else:
        raise ValueError(cfg.family)
    return defs


# ---------------------------------------------------------------------------
# Schema consumers
# ---------------------------------------------------------------------------


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _map_defs(fn: Callable[[ParamDef], Any], defs) -> Any:
    if _is_def(defs):
        return fn(defs)
    return {k: _map_defs(fn, v) for k, v in defs.items()}


def _leaf_dtype(cfg: ModelConfig, d: ParamDef):
    if d.dtype is not None:
        return jnp.dtype(d.dtype)
    if d.init in ("ones", "a_log", "dt_bias"):
        return jnp.float32  # norms/ssm scalars stay f32
    return jnp.dtype(cfg.dtype)


def param_shapes(cfg: ModelConfig):
    """ShapeDtypeStruct tree — the dry-run path (no allocation)."""
    return _map_defs(
        lambda d: jax.ShapeDtypeStruct(d.shape, _leaf_dtype(cfg, d)), param_defs(cfg)
    )


def param_pspecs(cfg: ModelConfig, rules: ShardingRules, mesh: Mesh):
    return _map_defs(
        lambda d: spec_for(d.logical, rules, mesh, d.shape), param_defs(cfg)
    )


def param_shardings(cfg: ModelConfig, rules: ShardingRules, mesh: Mesh):
    return _map_defs(
        lambda d: NamedSharding(mesh, spec_for(d.logical, rules, mesh, d.shape)),
        param_defs(cfg),
    )


def init_params(cfg: ModelConfig, key: jax.Array):
    defs = param_defs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))

    def init_one(d: ParamDef, k):
        dt = _leaf_dtype(cfg, d)
        if d.init == "zeros":
            return jnp.zeros(d.shape, dt)
        if d.init == "ones":
            return jnp.ones(d.shape, dt)
        if d.init == "a_log":
            nh = d.shape[-1]
            base = jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32))
            return jnp.broadcast_to(base, d.shape).astype(dt)
        if d.init == "dt_bias":
            return jnp.full(d.shape, -4.6, dt)  # softplus^-1(~0.01)
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        scale = 1.0 / np.sqrt(max(fan_in, 1))
        return (scale * jax.random.normal(k, d.shape, jnp.float32)).astype(dt)

    inited = [init_one(d, k) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, inited)


def param_count_actual(cfg: ModelConfig) -> int:
    tree = param_shapes(cfg)
    return int(
        sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(tree))
    )


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _embed(cfg, mesh, rules, params, tokens=None, embeds=None):
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    else:
        x = params["embed"]["table"][tokens]
    return constrain(x, tfm.residual_logical(cfg), rules, mesh)


def _lm_head(cfg, mesh, rules, params, x):
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = h @ params["lm_head"]["w"]
    return logits  # (b, s, Vp)


def _barrier(tree):
    """optimization_barrier at layer-scan boundaries: prevents XLA's convert
    sinking from upcasting whole stacked bf16 carry/ys buffers to f32 (a
    multi-GiB pessimization observed on the host backend), and pins the
    remat save points. Skips None leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    leaves = list(jax.lax.optimization_barrier(tuple(leaves)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    # "full": save only block boundaries PLUS explicitly named cross-device
    # scan results (SSD inter-chunk states) — recomputing those would repeat
    # their collectives; archs without named values behave as plain full
    # remat (the policy saves nothing extra).
    return jax.checkpoint(
        fn,
        policy=jax.checkpoint_policies.save_only_these_names("ssd_scan_state"),
    )


def run_stack(
    cfg: ModelConfig,
    mesh: Mesh,
    rules: ShardingRules,
    params,
    tokens=None,
    embeds=None,
    mode: str = "train",
    cache=None,
    pos=None,
):
    """Embed + all blocks; returns (hidden, new_cache, aux_loss). The LM head
    is applied by the caller (chunked for training CE; last-token-only for
    prefill) — keeps the (b, s, Vp) logits tensor from ever materializing."""
    x = _embed(cfg, mesh, rules, params, tokens, embeds)
    b, s = x.shape[0], x.shape[1]
    if mode == "decode":
        positions = jnp.full((1,), pos, dtype=jnp.int32)
    else:
        positions = jnp.arange(s, dtype=jnp.int32)
    zero = jnp.zeros((), jnp.float32)
    x = pack_bf16(x)  # u16 storage across scan boundaries (see layers.py)

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        stacked = params["layers"]

        def body(carry, p_l, cache_l):
            x, aux = carry
            # barrier the sliced layer params: blocks loop-invariant code
            # motion from hoisting an f32 convert of the WHOLE stacked weight
            # array out of the scan (host-backend artifact, +2x param bytes).
            p_l = _barrier(p_l)
            x = unpack_bf16(x)
            x, new_cache_l, aux_l = tfm.dense_block(
                cfg, mesh, rules, p_l, x, positions, mode, cache_l, pos
            )
            x, new_cache_l = _barrier((x, new_cache_l))
            x = pack_bf16(x)
            return (x, aux + aux_l), new_cache_l

        if mode == "train":
            bf = _maybe_remat(cfg, lambda c, p_l: body(c, p_l, None))
            (x, aux), _ = jax.lax.scan(bf, (x, zero), stacked)
            new_cache = None
        elif mode == "prefill":
            (x, aux), new_cache = jax.lax.scan(
                lambda c, p_l: body(c, p_l, None), (x, zero), stacked
            )
        else:  # decode
            (x, aux), new_cache = jax.lax.scan(
                lambda c, xs: body(c, xs[0], xs[1]), (x, zero), (stacked, cache)
            )

    elif cfg.family == "ssm":
        stacked = params["layers"]
        aux = zero

        def body_ssm(x, p_l, state_l):
            p_l = _barrier(p_l)
            x = unpack_bf16(x)
            x, new_state = tfm.ssm_block(cfg, mesh, rules, p_l, x, mode, state_l)
            x, new_state = _barrier((x, new_state))
            return pack_bf16(x), new_state

        if mode == "train":
            bf = _maybe_remat(cfg, lambda x_, p_l: body_ssm(x_, p_l, None))
            x, _ = jax.lax.scan(bf, x, stacked)
            new_cache = None
        elif mode == "prefill":
            x, new_cache = jax.lax.scan(
                lambda c, p_l: body_ssm(c, p_l, None), x, stacked
            )
        else:
            x, new_cache = jax.lax.scan(
                lambda c, xs: body_ssm(c, xs[0], xs[1]), x, (stacked, cache)
            )

    elif cfg.family == "hybrid":
        stacked = params["layers"]
        shared = params["shared"]
        aux = zero

        def body_hy(x, p_sb, cache_sb):
            p_sb = _barrier(p_sb)
            x = unpack_bf16(x)
            ssm_states = cache_sb["ssm"] if cache_sb is not None else None
            attn_cache = cache_sb["attn"] if cache_sb is not None else None
            x, new_states, new_attn = tfm.hybrid_superblock(
                cfg, mesh, rules, p_sb, shared, x, positions, mode,
                ssm_states, attn_cache, pos,
            )
            out_cache = None
            if new_states is not None or new_attn is not None:
                out_cache = {"ssm": new_states, "attn": new_attn}
            x, out_cache = _barrier((x, out_cache))
            return pack_bf16(x), out_cache

        if mode == "train":
            bf = _maybe_remat(cfg, lambda x_, p_sb: body_hy(x_, p_sb, None))
            x, _ = jax.lax.scan(bf, x, stacked)
            new_cache = None
        elif mode == "prefill":
            x, new_cache = jax.lax.scan(
                lambda c, p_sb: body_hy(c, p_sb, None), x, stacked
            )
        else:
            x, new_cache = jax.lax.scan(
                lambda c, xs: body_hy(c, xs[0], xs[1]), x, (stacked, cache)
            )
    else:
        raise ValueError(cfg.family)

    return unpack_bf16(x), new_cache, aux


def forward(
    cfg: ModelConfig,
    mesh: Mesh,
    rules: ShardingRules,
    params,
    tokens=None,
    embeds=None,
    mode: str = "train",
    cache=None,
    pos=None,
):
    """Convenience full-logits forward. Returns (logits, new_cache, aux)."""
    x, new_cache, aux = run_stack(
        cfg, mesh, rules, params, tokens, embeds, mode, cache, pos
    )
    logits = _lm_head(cfg, mesh, rules, params, x)
    return logits, new_cache, aux


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy: bounds live logits to seq/LOSS_CHUNKS)
# ---------------------------------------------------------------------------

LOSS_CHUNKS = 8
AUX_WEIGHT = 0.01


def loss_from_hidden(cfg, mesh, rules, params, x, labels, aux):
    b, s, _ = x.shape
    chunks = LOSS_CHUNKS if (s % LOSS_CHUNKS == 0 and s >= LOSS_CHUNKS) else 1
    cs = s // chunks
    total = jnp.zeros((), jnp.float32)
    for c in range(chunks):
        logits_c = _lm_head(cfg, mesh, rules, params, x[:, c * cs : (c + 1) * cs])
        total = total + softmax_cross_entropy(
            logits_c, labels[:, c * cs : (c + 1) * cs], cfg.vocab_size
        )
    return total / chunks + AUX_WEIGHT * aux


# ---------------------------------------------------------------------------
# Steps (built per (cfg, mesh, rules); jit happens at the call site with
# in_shardings from input_specs)
# ---------------------------------------------------------------------------


def make_loss_fn(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules):
    def loss_fn(params, batch):
        x, _, aux = run_stack(
            cfg, mesh, rules, params,
            tokens=batch.get("tokens"), embeds=batch.get("embeds"), mode="train",
        )
        return loss_from_hidden(cfg, mesh, rules, params, x, batch["labels"], aux)

    return loss_fn


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules):
    def prefill_step(params, batch):
        x, cache, _ = run_stack(
            cfg, mesh, rules, params,
            tokens=batch.get("tokens"), embeds=batch.get("embeds"), mode="prefill",
        )
        logits_last = _lm_head(cfg, mesh, rules, params, x[:, -1:, :])
        return logits_last[:, 0, :], cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules):
    def serve_step(params, cache, batch):
        x, new_cache, _ = run_stack(
            cfg, mesh, rules, params,
            tokens=batch.get("token"), embeds=batch.get("embed"),
            mode="decode", cache=cache, pos=batch["pos"],
        )
        logits = _lm_head(cfg, mesh, rules, params, x)
        return logits[:, -1, :], new_cache

    return serve_step
