"""Layer-stack machinery for all assigned families.

A model is a list of *stages*; a stage is (period_spec, n_periods) where
period_spec is a tuple of (layer_type, ffn_kind) entries. Uniform stacks have
a 1-layer period scanned n times (compile once per layer type); Jamba's 1:7
hybrid is an 8-layer period scanned 9 times. Params for a stage are stacked
pytrees with a leading period axis; train/prefill/decode all run as
lax.scan over that axis (remat per period for training).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from . import sharding
from .attention import (
    attn_init, attn_forward, attn_prefill, attn_decode, cross_attn_forward,
    flash_attention,
)
from .layers import glu_mlp, glu_mlp_init, rmsnorm, rmsnorm_init
from .mamba import mamba_init, mamba_forward, mamba_decode
from .moe import moe_init, moe_forward

Spec = Tuple[Tuple[str, Optional[str]], ...]


def build_stages(cfg) -> List[Tuple[Spec, int]]:
    if cfg.family in ("dense", "vlm"):
        return [((("attn", "mlp"),), cfg.num_layers)]
    if cfg.family == "moe":
        stages = []
        fd = cfg.first_dense_layers
        if fd:
            stages.append(((("attn", "mlp"),), fd))
        stages.append(((("attn", "moe"),), cfg.num_layers - fd))
        return stages
    if cfg.family == "ssm":
        return [((("mamba", None),), cfg.num_layers)]
    if cfg.family == "hybrid":
        period = [("attn", "mlp")]
        for i in range(1, cfg.attn_period):
            period.append(("mamba", "moe" if i % 2 == 1 else "mlp"))
        assert cfg.num_layers % cfg.attn_period == 0
        return [(tuple(period), cfg.num_layers // cfg.attn_period)]
    if cfg.family == "audio":
        # decoder stack (encoder built separately)
        return [((("attn_cross", "mlp"),), cfg.num_layers)]
    raise ValueError(cfg.family)


def encoder_stages(cfg) -> List[Tuple[Spec, int]]:
    return [((("attn", "mlp"),), cfg.encoder_layers)]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def layer_init(key, cfg, ltype, ffn, dtype):
    p: Dict[str, Any] = {}
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if ltype in ("attn", "attn_cross"):
        p["ln1"] = rmsnorm_init(d, dtype)
        p["attn"] = attn_init(ks[0], cfg, dtype)
        if ltype == "attn_cross":
            p["ln_x"] = rmsnorm_init(d, dtype)
            p["xattn"] = attn_init(ks[1], cfg.with_(qk_norm=False), dtype)
    elif ltype == "mamba":
        p["ln1"] = rmsnorm_init(d, dtype)
        p["mamba"] = mamba_init(ks[0], cfg, dtype)
    if ffn == "mlp":
        p["ln2"] = rmsnorm_init(d, dtype)
        p["mlp"] = glu_mlp_init(ks[2], d, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["ln2"] = rmsnorm_init(d, dtype)
        p["moe"] = moe_init(ks[3], cfg, dtype)
    return p


def stage_init(key, cfg, spec: Spec, n: int, dtype):
    def one(k):
        ks = jax.random.split(k, len(spec))
        return {
            f"l{i}": layer_init(ks[i], cfg, lt, ffn, dtype)
            for i, (lt, ffn) in enumerate(spec)
        }

    return jax.vmap(one)(jax.random.split(key, n))


# --------------------------------------------------------------------------
# MoE dispatch wrapper (shard_map when a mesh is configured)
# --------------------------------------------------------------------------

_ROUTED = ("router", "w_gate", "w_up", "w_down")


def apply_moe(p, cfg, x):
    mesh = sharding.get_mesh()
    routed = {k: p[k] for k in _ROUTED}
    tp = sharding._STATE["tp"]
    if (
        mesh is None
        or tp not in mesh.axis_names
        or cfg.num_experts % mesh.shape[tp] != 0
    ):
        out = moe_forward(routed, cfg.with_(num_shared_experts=0), x)
    else:
        dp_size = 1
        for a in sharding._STATE["dp"]:
            if a in mesh.axis_names:
                dp_size *= mesh.shape[a]
        # decode batches (e.g. B=1 long-context) may not divide dp:
        # replicate tokens across dp in that case (experts still sharded).
        dp = (sharding.pspec("dp", None, None)
              if x.shape[0] % dp_size == 0
              else sharding.pspec(None, None, None))
        especs = {
            "router": P(None, None),
            "w_gate": P(tp, None, None),
            "w_up": P(tp, None, None),
            "w_down": P(tp, None, None),
        }
        out = jax.shard_map(
            lambda xx, pp: moe_forward(
                pp, cfg.with_(num_shared_experts=0), xx, axis_name=tp
            ),
            mesh=mesh,
            in_specs=(dp, especs),
            out_specs=dp,
        )(x, routed)
    if cfg.num_shared_experts:
        out = out + glu_mlp(p["shared"], x)
    return out


# --------------------------------------------------------------------------
# forward (no cache)
# --------------------------------------------------------------------------

def apply_layer(lp, cfg, lt, ffn, x, positions, memory=None, causal=True):
    if cfg.parallel_block and lt == "attn" and ffn == "mlp":
        # parallel residual: partial attn-out and partial mlp-out are summed
        # BEFORE replication, so the partitioner emits a single all-reduce.
        h = attn_forward(lp["attn"], cfg, rmsnorm(lp["ln1"], x), positions,
                         causal=causal)
        h = h + glu_mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
        return sharding.constrain(
            x + h, "dp", "tp" if cfg.seq_shard else None, None
        )
    if lt in ("attn", "attn_cross"):
        x = x + attn_forward(lp["attn"], cfg, rmsnorm(lp["ln1"], x),
                             positions, causal=causal)
        if lt == "attn_cross":
            x = x + cross_attn_forward(
                lp["xattn"], cfg, rmsnorm(lp["ln_x"], x), memory
            )
    elif lt == "mamba":
        x = x + mamba_forward(lp["mamba"], cfg, rmsnorm(lp["ln1"], x))[0]
    if ffn == "mlp":
        x = x + glu_mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
    elif ffn == "moe":
        x = x + apply_moe(lp["moe"], cfg, rmsnorm(lp["ln2"], x))
    return sharding.constrain(
        x, "dp", "tp" if cfg.seq_shard else None, None
    )


def stages_forward(stage_params, cfg, stages, x, positions, memory=None,
                   causal=True, remat=True):
    for (spec, _n), sp in zip(stages, stage_params):
        def body(x_, lp, spec=spec):
            for i, (lt, ffn) in enumerate(spec):
                x_ = apply_layer(lp[f"l{i}"], cfg, lt, ffn, x_, positions,
                                 memory=memory, causal=causal)
            return x_

        if remat and cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x, sp,
                            unroll=cfg.scan_unroll)
    return x


# --------------------------------------------------------------------------
# prefill / decode (KV + state caches)
# --------------------------------------------------------------------------

def layer_prefill(lp, cfg, lt, ffn, x, positions, memory=None):
    cache = {}
    if cfg.parallel_block and lt == "attn" and ffn == "mlp":
        h, (k, v) = attn_prefill(lp["attn"], cfg, rmsnorm(lp["ln1"], x),
                                 positions)
        cache["self_k"], cache["self_v"] = k, v
        h = h + glu_mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
        return sharding.constrain(
            x + h, "dp", "tp" if cfg.seq_shard else None, None
        ), cache
    if lt in ("attn", "attn_cross"):
        h, (k, v) = attn_prefill(lp["attn"], cfg, rmsnorm(lp["ln1"], x),
                                 positions)
        x = x + h
        cache["self_k"], cache["self_v"] = k, v
        if lt == "attn_cross":
            b = memory.shape[0]
            kvh, dh = cfg.num_kv_heads, cfg.head_dim
            ck = (memory @ lp["xattn"]["wk"]).reshape(b, -1, kvh, dh)
            cv = (memory @ lp["xattn"]["wv"]).reshape(b, -1, kvh, dh)
            cache["cross_k"], cache["cross_v"] = ck, cv
            xq = rmsnorm(lp["ln_x"], x)
            x = x + cross_attn_forward(lp["xattn"], cfg, xq, memory)
    elif lt == "mamba":
        h, mcache = mamba_forward(lp["mamba"], cfg, rmsnorm(lp["ln1"], x))
        x = x + h
        cache["mamba"] = mcache
    if ffn == "mlp":
        x = x + glu_mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
    elif ffn == "moe":
        x = x + apply_moe(lp["moe"], cfg, rmsnorm(lp["ln2"], x))
    return sharding.constrain(
        x, "dp", "tp" if cfg.seq_shard else None, None
    ), cache


def _cross_decode(p, cfg, x, ck, cv):
    b = x.shape[0]
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, h, dh)
    out = flash_attention(q, ck, cv, causal=False)
    return out.reshape(b, 1, h * dh) @ p["wo"]


def layer_decode(lp, cfg, lt, ffn, x, cache, pos):
    new_cache = {}
    if lt in ("attn", "attn_cross"):
        h, (k, v) = attn_decode(
            lp["attn"], cfg, rmsnorm(lp["ln1"], x),
            (cache["self_k"], cache["self_v"]), pos,
        )
        x = x + h
        new_cache["self_k"], new_cache["self_v"] = k, v
        if lt == "attn_cross":
            xq = rmsnorm(lp["ln_x"], x)
            x = x + _cross_decode(lp["xattn"], cfg, xq,
                                  cache["cross_k"], cache["cross_v"])
            new_cache["cross_k"] = cache["cross_k"]
            new_cache["cross_v"] = cache["cross_v"]
    elif lt == "mamba":
        h, mcache = mamba_decode(lp["mamba"], cfg, rmsnorm(lp["ln1"], x),
                                 cache["mamba"])
        x = x + h
        new_cache["mamba"] = mcache
    if ffn == "mlp":
        x = x + glu_mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
    elif ffn == "moe":
        x = x + apply_moe(lp["moe"], cfg, rmsnorm(lp["ln2"], x))
    return x, new_cache


def _period_prefill(lp, cfg, spec, x, positions, memory):
    caches = {}
    for i, (lt, ffn) in enumerate(spec):
        x, c = layer_prefill(lp[f"l{i}"], cfg, lt, ffn, x, positions, memory)
        caches[f"l{i}"] = c
    return x, caches


def _period_decode(lp, cfg, spec, x, cache, pos):
    new = {}
    for i, (lt, ffn) in enumerate(spec):
        x, c = layer_decode(lp[f"l{i}"], cfg, lt, ffn, x, cache[f"l{i}"], pos)
        new[f"l{i}"] = c
    return x, new


def stages_prefill(stage_params, cfg, stages, x, positions, memory=None):
    caches = []
    for (spec, _n), sp in zip(stages, stage_params):
        def body(x_, lp, spec=spec):
            return _period_prefill(lp, cfg, spec, x_, positions, memory)

        x, cache = jax.lax.scan(body, x, sp, unroll=cfg.scan_unroll)
        caches.append(cache)
    return x, caches


def stages_decode(stage_params, cfg, stages, x, caches, pos):
    new_caches = []
    for (spec, _n), sp, cache in zip(stages, stage_params, caches):
        def body(x_, inp, spec=spec):
            lp, cl = inp
            return _period_decode(lp, cfg, spec, x_, cl, pos)

        x, new = jax.lax.scan(body, x, (sp, cache),
                              unroll=cfg.scan_unroll)
        new_caches.append(new)
    return x, new_caches
