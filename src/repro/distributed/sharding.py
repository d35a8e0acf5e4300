"""Logical-axis sharding rules (MaxText-style) + activation constraints.

Model code annotates tensors with *logical* axis names; a rule table maps the
logical names to mesh axes.  ``use_mesh(mesh, rules)`` activates constraints;
outside the context (e.g. single-device CPU smoke tests) ``shard()`` is a
no-op, so the same model code runs everywhere.

Rule sets
---------
``RULES_TP_DP``      — production default: batch→data(+pod), TP width→model.
``RULES_LONG_CTX``   — long_500k decode: batch=1, so the *KV sequence* dim is
                       sharded over data (flash-decoding-style distributed
                       softmax; see models/attention.py lse-combine path).
``RULES_SINGLE``     — everything replicated (debug).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, tuple[str, ...], None]

# logical axis -> mesh axis (None = replicated). "data+" expands to
# ("pod", "data") when the mesh has a pod axis, else "data".
RULES_TP_DP: dict[str, MeshAxes] = {
    # activations
    "batch": "data+",
    "act_seq": None,
    "kv_seq": None,
    "embed": None,
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_ff": "model",
    "act_vocab": "model",
    "act_experts": "model",
    "act_inner": "model",       # mamba/xlstm expanded inner dim
    "act_dv": "model",          # mLSTM value dim
    # weights
    "w_embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "inner": "model",
    "head_dim": None,
    "state": None,
    "lora": None,
    "dv": "model",
}

RULES_LONG_CTX: dict[str, MeshAxes] = dict(
    RULES_TP_DP,
    batch=None,                  # global_batch=1: nothing to shard
    kv_seq="data",               # shard the 524k-token KV cache over data
)

RULES_SINGLE: dict[str, MeshAxes] = {k: None for k in RULES_TP_DP}


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: dict[str, MeshAxes] = {}


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict[str, MeshAxes]] = None):
    """Activate sharding constraints for model code within this context."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = dict(rules if rules is not None else RULES_TP_DP)
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _resolve(axis: Optional[str], mesh: Mesh) -> MeshAxes:
    if axis is None:
        return None
    rule = _CTX.rules.get(axis, None)
    if rule == "data+":
        return ("pod", "data") if "pod" in mesh.axis_names else "data"
    if rule == "all":
        return tuple(mesh.axis_names)
    return rule


def logical_to_pspec(axes: tuple[Optional[str], ...], mesh: Mesh,
                     rules: Optional[dict[str, MeshAxes]] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec under `rules`."""
    if rules is None:
        rules = _CTX.rules or RULES_TP_DP
    out = []
    used: set[str] = set()
    for a in axes:
        r = rules.get(a, None) if a is not None else None
        if r == "data+":
            r = ("pod", "data") if "pod" in mesh.axis_names else "data"
        elif r == "all":
            r = tuple(mesh.axis_names)
        # a mesh axis may appear only once in a PartitionSpec
        if r is not None:
            flat = (r,) if isinstance(r, str) else tuple(r)
            if any(f in used for f in flat):
                r = None
            else:
                used.update(flat)
        out.append(r)
    return P(*out)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply with_sharding_constraint according to the active rule table.

    No-op outside a ``use_mesh`` context or when the mesh is trivial.
    """
    mesh = _CTX.mesh
    if mesh is None or mesh.size == 1:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard(): {len(axes)} axes for rank-{x.ndim} tensor")
    spec = logical_to_pspec(tuple(axes), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *axes: Optional[str],
                   rules: Optional[dict[str, MeshAxes]] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_pspec(tuple(axes), mesh, rules))
