"""Activation sharding constraints (GSPMD hints inside model code).

Model code calls these unconditionally; they no-op when no mesh is set
(``jax.set_mesh``; single-device smoke tests) and otherwise pin the
canonical layout:

    batch over ("pod","data");  heads / experts / ffn-hidden over "model".

Without these, GSPMD propagation can drop the batch sharding inside
scan-of-remat bodies (observed: replicated (B,S,V) logits and attention
scores — 100s of GiB/device on the dry-run meshes).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The mesh set by ``jax.set_mesh``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _fits(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


def _fits_uneven(dim: int, size: int) -> bool:
    """GSPMD pads uneven shardings; allow when pad waste stays under ~2x
    (e.g. 40 heads over 16 shards -> padded to 48, 1.2x; 14 -> 16, 1.14x)."""
    return size > 0 and (dim % size == 0 or dim >= size // 2)


def constrain(x: jax.Array, *axes) -> jax.Array:
    """axes: per-dim entries of None | 'batch' | 'model' | explicit tuple."""
    mesh = current_mesh()
    if mesh is None:
        return x
    dp = _dp_axes(mesh)
    spec = []
    for dim, a in zip(x.shape, axes):
        if a == "batch":
            size = 1
            for ax in dp:
                size *= mesh.shape[ax]
            spec.append(dp if dp and _fits(dim, size) else None)
        elif a == "model":
            ok = ("model" in mesh.axis_names
                  and _fits_uneven(dim, mesh.shape["model"]))
            spec.append("model" if ok else None)
        else:
            spec.append(a)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def constrain_bsd(x: jax.Array) -> jax.Array:
    """(B, S, d) activations: batch over dp, d replicated."""
    return constrain(x, "batch", None, None)


def constrain_heads(x: jax.Array) -> jax.Array:
    """(B, S, H, hd): batch over dp, heads over model."""
    return constrain(x, "batch", None, "model", None)


def constrain_params(tree):
    """Pin param shardings inside scan bodies. with_sharding_constraint
    transposes to itself, so the params' COTANGENTS (gradients accumulated by
    the scan backward) inherit the same sharding — without this, nested-scan
    MoE weight grads materialize fully replicated (observed: 36 GiB/device
    f32 expert-grad buffers on the dry-run meshes)."""
    mesh = current_mesh()
    if mesh is None:
        return tree
    from .sharding import param_partition_spec
    dp = _dp_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None

    def one(kp, leaf):
        parts = [str(k).strip(".[]'\"") for k in kp]
        path = "/".join(parts)
        spec = param_partition_spec(path, leaf.shape, mesh, dp, tp)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, tree)
