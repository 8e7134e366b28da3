"""Parameter sharding rules: tree-path pattern -> logical spec -> PartitionSpec.

FSDP/ZeRO-3: weight matrices shard their d_model-like dim over the ``fsdp``
axes (data, and pod when multi-pod) and their TP dim over ``model``.  A
divisibility check demotes any dim that does not divide the mesh axis size to
replicated (e.g. whisper's 20 heads, granite's single KV head) — the generic
mechanism that makes all ten archs shardable with one rule table.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .api import DEFAULT_RULES, MULTIPOD_RULES, Axis


def get_shard_map():
    """The ``shard_map`` transform (:func:`jax.shard_map`)."""
    return jax.shard_map


def block_cyclic_perm(n: int, n_shards: int, block: int):
    """Row permutation realising a block-cyclic layout under contiguous sharding.

    Returns ``(perm, inv)`` (numpy int arrays, length ``n``) such that when
    ``x[perm]`` is sharded into ``n_shards`` equal contiguous pieces, shard
    ``i`` holds the *blocks* ``i, i + n_shards, i + 2·n_shards, …`` of the
    original ``x`` (blocks of ``block`` consecutive rows dealt round-robin —
    ScaLAPACK-style block-cyclic).  ``inv`` undoes it: ``x[perm][inv] == x``.

    ``n`` must be divisible by ``n_shards · block`` (pad first); the cyclic
    deal is what keeps the *symmetric* Gram's triangular tile grid balanced
    across shards — contiguous row blocks would give the last shard ~2×
    the PDE solves of the first.
    """
    if n % (n_shards * block) != 0:
        raise ValueError(
            f"block_cyclic_perm needs n divisible by n_shards*block, got "
            f"n={n}, n_shards={n_shards}, block={block}")
    n_blocks = n // block
    # shard i's blocks, concatenated shard-by-shard
    order = np.arange(n_blocks).reshape(-1, n_shards).T.reshape(-1)
    perm = (order[:, None] * block + np.arange(block)[None, :]).reshape(-1)
    inv = np.argsort(perm)
    return perm, inv


def gram_specs(mesh: Mesh, Bx: int, By: int, *,
               row_axis: str = "data", col_axis: str = "model"
               ) -> Tuple[P, P, P]:
    """PartitionSpecs ``(rows_spec, cols_spec, gram_spec)`` for a (Bx, By)
    Gram tiling: X rows over ``row_axis``, Y rows over ``col_axis``, the
    Gram over both.  Reuses :func:`physical_spec`'s divisibility demotion —
    a batch that does not divide its mesh axis is replicated instead of
    erroring, so the same call works on any device count.
    """
    rules = {"batch": row_axis, "model": col_axis, None: None}
    rows = physical_spec(("batch",), (Bx,), mesh, rules)
    cols = physical_spec(("model",), (By,), mesh, rules)
    gram = physical_spec(("batch", "model"), (Bx, By), mesh, rules)
    return rows, cols, gram


# logical specs by trailing path name; rank refers to the UNSTACKED param
_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "table":      ("model", "fsdp"),          # embeddings: vocab x d_model
    "wq":         ("fsdp", "model", None),
    "wk":         ("fsdp", "model", None),
    "wv":         ("fsdp", "model", None),
    "wo":         ("model", None, "fsdp"),
    "bq":         ("model", None),
    "bk":         ("model", None),
    "bv":         ("model", None),
    "w_gate":     ("fsdp", "model"),
    "w_up":       ("fsdp", "model"),
    "w_in":       ("fsdp", "model"),
    "w_out":      ("model", "fsdp"),
    "router":     ("fsdp", None),
    "shared_gate": ("fsdp", None),
    "patch_proj": (None, "fsdp"),
    "sig_proj":   (None, None),
    # mamba2 (packed projections: replicate TP, shard over fsdp only)
    "in_proj":    ("fsdp", None),
    "out_proj":   (None, "fsdp"),
    "conv_w":     (None, "model"),
    "conv_b":     ("model",),
    "A_log":      (None,),
    "D":          (None,),
    "dt_bias":    (None,),
    # rg-lru
    "w_x":        ("fsdp", "model"),
    "w_y":        ("fsdp", "model"),
    "w_a":        (None, "model"),
    "w_i":        (None, "model"),
    "b_a":        ("model",),
    "b_i":        ("model",),
    "lam":        ("model",),
    # norms
    "scale":      (None,),
    "bias":       (None,),
}

# MoE expert tensors (parent name "moe"): (E, D, F) / (E, F, D).
# The F dim lists "model" as a fallback: when the expert count does not
# divide the model axis (e.g. Qwen's 60 experts), the per-expert hidden is
# tensor-parallel instead — the used-axis bookkeeping in physical_spec picks
# exactly one of the two automatically.
_MOE_RULES = {
    "w_gate": ("expert", "fsdp", "model"),
    "w_up":   ("expert", "fsdp", "model"),
    "w_out":  ("expert", "model", "fsdp"),
}


def rules_for(cfg, multi_pod: bool) -> Dict[Optional[str], Axis]:
    """Logical -> physical mapping, with per-family overrides."""
    base = dict(MULTIPOD_RULES if multi_pod else DEFAULT_RULES)
    if cfg is not None and getattr(cfg, "family", None) == "ssm":
        # mamba2: packed projections are not TP-friendly; use the model axis
        # as extra batch/FSDP parallelism, but keep it available for the
        # embedding/logits vocab dim and the residual-stream sequence dim
        # (DESIGN.md §Arch-applicability).
        base["batch"] = (("pod", "data") if multi_pod else ("data", "model"))
        base["fsdp"] = (("pod", "data", "model") if multi_pod
                        else ("data", "model"))
        base["expert"] = None
    return base


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return math.prod(mesh.shape[a] for a in axis)


def logical_spec_for(path: Tuple[str, ...], leaf) -> Tuple[Optional[str], ...]:
    name = path[-1]
    parent = path[-2] if len(path) > 1 else ""
    grandparent = path[-3] if len(path) > 2 else ""
    if name in _MOE_RULES and ("moe" in (parent, grandparent)):
        base = _MOE_RULES[name]
    elif name in _RULES:
        base = _RULES[name]
    else:
        base = (None,) * leaf.ndim
    if leaf.ndim == len(base) + 1:          # scan-stacked: leading layer dim
        base = (None,) + base
    elif leaf.ndim != len(base):            # unexpected rank: replicate
        base = (None,) * leaf.ndim
    return base


def physical_spec(logical: Tuple[Optional[str], ...], shape, mesh: Mesh,
                  rules: Dict[Optional[str], Axis]) -> P:
    out = []
    used: set = set()
    for dim, name in zip(shape, logical):
        axis = rules.get(name, None)
        axes = (axis,) if isinstance(axis, str) else tuple(axis or ())
        axes = tuple(a for a in axes if a not in used)
        # progressively drop trailing axes until the dim divides the product
        while axes and dim % math.prod(mesh.shape[a] for a in axes) != 0:
            axes = axes[:-1]
        if axes:
            out.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            out.append(None)
    return P(*out)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def param_shardings(params_shape, cfg, mesh: Mesh, multi_pod: bool):
    """Tree of NamedSharding for a params (or ShapeDtypeStruct) tree."""
    rules = rules_for(cfg, multi_pod)

    def one(path, leaf):
        logical = logical_spec_for(_path_names(path), leaf)
        return NamedSharding(mesh, physical_spec(logical, leaf.shape, mesh, rules))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_shardings(batch_shape, cfg, mesh: Mesh, multi_pod: bool):
    """Inputs: batch dim over the batch axes, everything else replicated."""
    rules = rules_for(cfg, multi_pod)

    def one(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(
            mesh, physical_spec(("batch",) + (None,) * (leaf.ndim - 1),
                                leaf.shape, mesh, rules))

    return jax.tree.map(one, batch_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# logical specs for decode-cache leaves, keyed by leaf name (UNSTACKED rank)
_CACHE_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "k":    ("batch", None, "model", None),     # (B, S, KV, hd)
    "v":    ("batch", None, "model", None),
    "ck":   ("batch", None, "model", None),     # whisper cross K/V
    "cv":   ("batch", None, "model", None),
    "pos":  (None,),                            # ring positions (W,)
    "conv": ("batch", None, "model"),           # conv tail (B, K, C)
    "state": ("batch", "model", None, None),    # ssm state (B, H, N, P)
    "h":    ("batch", "model"),                 # rg-lru state (B, W)
}


def cache_shardings(cache_shape, cfg, mesh: Mesh, multi_pod: bool):
    """NamedShardings for decode caches (batch dim is NOT dim 0 when layers
    are scan-stacked — handled via the rank adjustment)."""
    rules = rules_for(cfg, multi_pod)

    def one(path, leaf):
        names = _path_names(path)
        name = names[-1]
        base = _CACHE_RULES.get(name, None)
        if base is None:
            logical = (None,) * leaf.ndim
        else:
            logical = base
            if name in ("k", "v", "ck", "cv"):
                # TP the cache on KV heads when they divide the model axis;
                # else shard the SEQUENCE dim (flash-decoding style: scores
                # stay seq-sharded, softmax reduces via tiny collectives);
                # else head_dim.  A replicated cache wastes the whole model
                # axis of HBM (DESIGN.md §6).
                S, kv, hd = leaf.shape[-3], leaf.shape[-2], leaf.shape[-1]
                tp = _axis_size(mesh, rules.get("model"))
                if tp > 1 and kv % tp != 0:
                    if S % tp == 0:
                        logical = ("batch", "model", None, None)
                    elif hd % tp == 0:
                        logical = ("batch", None, None, "model")
            if leaf.ndim == len(logical) + 1:   # stacked over layers
                logical = (None,) + tuple(logical)
            elif leaf.ndim != len(logical):
                logical = (None,) * leaf.ndim
        return NamedSharding(mesh, physical_spec(logical, leaf.shape, mesh,
                                                 rules))

    return jax.tree_util.tree_map_with_path(one, cache_shape)
