"""Logical-axis sharding helpers.

Logical axes: 'dp' (batch / FSDP shard axis -> physical ('pod', 'data')),
'tp' (tensor/expert parallel -> physical 'model'). Models only speak logical
axes; this module resolves them against the active mesh configuration, and
every helper degrades to a no-op when no mesh is configured (single-device
smoke tests)."""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = {"mesh": None, "dp": ("pod", "data"), "tp": "model"}


def set_mesh(mesh: Optional[Mesh], dp=None, tp=None) -> None:
    _STATE["mesh"] = mesh
    if mesh is not None:
        names = mesh.axis_names
        if dp is None:
            dp = tuple(n for n in names if n != "model")
        if tp is None:
            tp = "model" if "model" in names else None
        _STATE["dp"] = tuple(dp) if isinstance(dp, (list, tuple)) else (dp,)
        _STATE["tp"] = tp


def get_mesh() -> Optional[Mesh]:
    return _STATE["mesh"]


def _resolve(axis):
    if axis is None:
        return None
    if axis == "dp":
        dp = _STATE["dp"]
        return dp if len(dp) > 1 else dp[0]
    if axis == "tp":
        return _STATE["tp"]
    return axis


def pspec(*axes) -> P:
    return P(*[_resolve(a) for a in axes])


def constrain(x, *axes):
    """with_sharding_constraint on logical axes; no-op without a mesh."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, pspec(*axes))
    )


def named(*axes) -> Optional[NamedSharding]:
    mesh = _STATE["mesh"]
    if mesh is None:
        return None
    return NamedSharding(mesh, pspec(*axes))


# --------------------------------------------------------------------------
# Parameter sharding rules (FSDP over 'dp' + tensor/expert parallel on 'tp')
# --------------------------------------------------------------------------

_RULES = {
    # (parent, name) or name -> logical axes for the *unstacked* leaf
    "embed": ("tp", "dp"),
    "lm_head": ("dp", "tp"),
    "final_norm": (None,),
    "wq": ("dp", "tp"), "wk": ("dp", "tp"), "wv": ("dp", "tp"),
    "wo": ("tp", "dp"),
    "bq": ("tp",), "bk": ("tp",), "bv": ("tp",),
    "q_norm": (None,), "k_norm": (None,),
    "ln1": (None,), "ln2": (None,), "ln_x": (None,),
    "w_gate": ("dp", "tp"), "w_up": ("dp", "tp"), "w_down": ("tp", "dp"),
    ("moe", "router"): ("dp", None),
    ("moe", "w_gate"): ("tp", "dp", None),
    ("moe", "w_up"): ("tp", "dp", None),
    ("moe", "w_down"): ("tp", None, "dp"),
    "in_z": ("dp", "tp"), "in_x": ("dp", "tp"), "in_dt": ("dp", "tp"),
    "in_b": ("dp", None), "in_c": ("dp", None),
    "conv_x": (None, "tp"), "conv_b": (None, None), "conv_c": (None, None),
    "conv_bias_x": ("tp",), "conv_bias_b": (None,), "conv_bias_c": (None,),
    "a_log": ("tp",), "d_skip": ("tp",), "dt_bias": ("tp",),
    "norm_w": ("tp",), "out_proj": ("tp", "dp"),
}


def _leaf_rule(path, leaf):
    keys = [p.key for p in path if hasattr(p, "key")]
    name = keys[-1] if keys else ""
    parent = keys[-2] if len(keys) >= 2 else ""
    rule = _RULES.get((parent, name), _RULES.get(name))
    if rule is None:
        rule = (None,) * leaf.ndim
    # stacked stage leaves carry a leading period axis
    pad = leaf.ndim - len(rule)
    rule = (None,) * pad + tuple(rule)
    return pspec(*rule)


def param_pspecs(params):
    """PartitionSpec tree matching a (possibly abstract) param tree."""
    return jax.tree_util.tree_map_with_path(_leaf_rule, params)


def param_shardings(params):
    mesh = _STATE["mesh"]
    assert mesh is not None
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _leaf_rule(p, l)), params
    )
