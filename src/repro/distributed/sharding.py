"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Model code annotates every parameter dimension with a *logical* axis name
("embed", "ff", "vocab", "expert", ...).  This module maps logical names to
mesh axes per architecture:

* **TP** ("model" axis): attention head projections, MLP hidden, vocab.
* **EP** ("data" axis): MoE expert dim — each data shard owns E/16 experts
  and GSPMD emits the dispatch/combine all-to-all between the token-sharded
  and expert-sharded layouts.
* **FSDP** (("pod","data")): the `embed` dim of weight matrices for the
  archs whose parameters cannot live TP-only (kimi-k2 1T, llama4-scout,
  chameleon-34b), and for every arch on a pure data-parallel mesh of more
  than one device (no "model" axis divides the weights there).  With
  scan-over-layers this yields the per-layer all-gather / reduce-scatter
  schedule of ZeRO-3.
* **ZeRO-1** optimizer extension: optimizer-state (and gradient-accumulator)
  leaves additionally shard their largest still-replicated divisible dim
  over ("pod","data").

The same rules drive: parameter shardings, optimizer-state shardings, input
batch specs, KV-cache specs, and the ``constrain`` hints inside model code.
"""
from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

PyTree = Any
MeshAxes = Union[None, str, Tuple[str, ...]]

# archs whose parameter memory requires FSDP over the batch axes
FSDP_ARCHS = ("kimi-k2-1t-a32b", "llama4-scout-17b-a16e", "chameleon-34b")


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def uses_fsdp(cfg: ModelConfig, mesh: Mesh) -> bool:
    """Shard weights over the batch axes (ZeRO-3)?  Yes for the archs
    whose parameters need it at production scale, and on a mesh whose
    "model" axis is 1 but whose batch axes are not: replicated f32
    parameters, gradients and AdamW moments of hymba-1.5b do not fit a
    16 GB v5e chip of a 2x2 host, even with the moments ZeRO-1-sharded."""
    if cfg.name in FSDP_ARCHS:
        return True
    shards = int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))
    return shards > 1 and mesh.shape.get("model", 1) == 1


def rules_for(cfg: ModelConfig, mesh: Mesh) -> Dict[str, MeshAxes]:
    """Logical-axis -> mesh-axes mapping for this arch on this mesh."""
    batch = batch_axes(mesh)
    fsdp = uses_fsdp(cfg, mesh)
    rules: Dict[str, MeshAxes] = {
        "layers": None,
        "embed": batch if fsdp else None,
        "q_proj": "model",
        "kv_proj": "model",
        "heads": "model",
        "kv_heads": "model",
        "kv_hd": "model",      # cache head_dim fallback ('kv_cache_hd' flag)
        "ff": "model",
        "vocab": "model",
        "ssm_inner": "model",
        "ssm_bc": "model",
        "ssm_heads": "model",
        # MoE: EP over the data axis; expert-ff TP over model.  With the
        # 'moe_2d_ep' flag (or 'moe_a2a' with padded storage), experts
        # shard over (data x model): the expert FFN is fully local and the
        # shard_map all-to-all consumes weights without resharding
        # (§Perf iters B4/B6).
        "expert": (("data", "model")
                   if ("moe_2d_ep" in cfg.perf_flags
                       or ("moe_a2a" in cfg.perf_flags and cfg.moe
                           and cfg.moe.num_experts >= 256))
                   and "data" in mesh.axis_names
                   else "data" if "data" in mesh.axis_names else None),
        "moe_dmodel": "model",   # dispatched-tensor d_model (RS not AR)
        # activations
        "batch": batch,
        "moe_groups": batch,
        "seq": None,
    }
    return rules


def spec_for(axes: Sequence[Optional[str]], rules: Mapping[str, MeshAxes],
             shape: Optional[Tuple[int, ...]] = None) -> P:
    """PartitionSpec from logical axes.

    Two safety rails, both revisited during perf hillclimbs (DESIGN.md §6):
    * non-divisible dims fall back to replicated (GSPMD would pad — wasted
      memory and bandwidth);
    * a mesh axis is given to at most one dim, left-to-right (e.g. kimi's
      expert tensors ask for 'data' via both EP and FSDP; EP wins and the
      FSDP entry keeps only its unused axes).
    """
    entries = []
    used: set = set()
    mesh = current_mesh()
    for i, ax in enumerate(axes):
        m = rules.get(ax) if ax is not None else None
        if m is None:
            entries.append(None)
            continue
        axes_tuple = (m,) if isinstance(m, str) else tuple(m)
        axes_tuple = tuple(a for a in axes_tuple if a not in used)
        if not axes_tuple:
            entries.append(None)
            continue
        if shape is not None and mesh is not None:
            prod = int(np.prod([mesh.shape[a] for a in axes_tuple]))
            if shape[i] % prod != 0:
                entries.append(None)
                continue
        used.update(axes_tuple)
        entries.append(axes_tuple if len(axes_tuple) > 1 else axes_tuple[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shardings_for(axes_tree: PyTree, params_tree: PyTree, mesh: Mesh,
                  rules: Mapping[str, MeshAxes]) -> PyTree:
    """NamedSharding tree matching ``params_tree`` from logical axes."""
    def one(axes, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        return NamedSharding(mesh, spec_for(tuple(axes), rules, shape))
    return jax.tree.map(one, axes_tree, params_tree,
                        is_leaf=lambda t: isinstance(t, tuple))


def zero1_shardings(param_shardings: PyTree, params_tree: PyTree, mesh: Mesh
                    ) -> PyTree:
    """Optimizer-state sharding: param sharding + extra batch-axes shard.

    For each leaf, shard the largest still-replicated dim divisible by the
    batch axes over ("pod","data") — classic ZeRO-1 partitioning expressed
    as GSPMD shardings (the reduce-scatter/all-gather pair appears in the
    lowered collective schedule).
    """
    batch = batch_axes(mesh)
    if not batch:
        return param_shardings
    denom = int(np.prod([mesh.shape[a] for a in batch]))

    def one(sh: NamedSharding, leaf):
        spec = list(sh.spec) + [None] * (leaf.ndim - len(sh.spec))
        # pick the largest replicated divisible dim
        best, best_size = None, 0
        for i, (entry, size) in enumerate(zip(spec, leaf.shape)):
            if entry is None and size % denom == 0 and size > best_size:
                best, best_size = i, size
        if best is not None:
            spec[best] = batch if len(batch) > 1 else batch[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, param_shardings, params_tree,
                        is_leaf=lambda t: isinstance(t, NamedSharding))


# ---------------------------------------------------------------------------
# Current-mesh registry (used by `constrain` inside model code)
# ---------------------------------------------------------------------------

_CURRENT: Dict[str, Any] = {"mesh": None, "rules": None}


class use_mesh_rules:
    """Context manager installing (mesh, rules) for ``constrain`` calls."""

    def __init__(self, mesh: Optional[Mesh], rules: Optional[Mapping] = None):
        self.mesh, self.rules = mesh, rules
        self._saved = None

    def __enter__(self):
        self._saved = dict(_CURRENT)
        _CURRENT["mesh"] = self.mesh
        _CURRENT["rules"] = self.rules
        return self

    def __exit__(self, *exc):
        _CURRENT.update(self._saved)
        return False


def current_mesh() -> Optional[Mesh]:
    return _CURRENT["mesh"]


def current_rules() -> Optional[Mapping[str, MeshAxes]]:
    return _CURRENT["rules"]


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]
              ) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh.

    Model code calls this at block boundaries so CPU tests run unchanged
    while the 512-chip lowering gets anchored activation layouts.
    """
    mesh, rules = _CURRENT["mesh"], _CURRENT["rules"]
    if mesh is None or rules is None:
        return x
    spec = spec_for(tuple(logical_axes), rules, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
