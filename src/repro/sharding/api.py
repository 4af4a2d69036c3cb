"""Logical-axis sharding: rules, activation constraints, param specs.

The model code never mentions mesh axes; it tags activations with
*logical* axes via ``logical_constraint(x, "batch", "seq", None)`` and
parameters get specs derived from their *path names* (``spec_for_path``).
The launcher binds logical axes to mesh axes with ``axis_rules``:

    with mesh, axis_rules(DEFAULT_RULES, mesh):
        jax.jit(train_step, in_shardings=..., ...)

Default binding (production mesh axes ``pod`` / ``data`` / ``model``):

    batch  -> (pod, data)     # DP across pods and within a pod
    vocab/heads/kv/ffn/expert/rnn -> model   # TP / EP
    ZeRO: largest remaining param dim -> data (FSDP + sharded opt state)

Every rule is divisibility-checked against the actual mesh so the same
model code lowers on any mesh (single host, 16x16 pod, 2x16x16
multi-pod) — non-divisible dims are left unsharded rather than erroring,
which is what makes elastic re-meshing across FL rounds possible.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections.abc import Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_CTX = threading.local()

# ZeRO/FSDP sharding applies only to params with at least this many
# elements (2M ~ a 1448^2 matrix); smaller tensors replicate.
ZERO_MIN_ELEMS = 2 ** 21

# logical axis -> mesh axis (or tuple of mesh axes)
DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "seq": None,
    "vocab": "model",
    "heads": "model",
    "kv": "model",
    "ffn": "model",
    "expert": "model",
    "rnn": "model",
    "d_model": None,
    "zero": "data",           # FSDP / optimizer-state axis
}


@contextlib.contextmanager
def axis_rules(rules: dict, mesh=None):
    prev = getattr(_CTX, "state", None)
    _CTX.state = (dict(rules), mesh)
    try:
        yield
    finally:
        _CTX.state = prev


def current_rules():
    return getattr(_CTX, "state", None)


def mesh_devices() -> int:
    """Devices of the mesh bound by :func:`axis_rules` (1 without)."""
    state = current_rules()
    mesh = state[1] if state is not None else None
    return 1 if mesh is None else int(mesh.size)


def _axis_size(mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        return int(np.prod([_axis_size(mesh, a) for a in name]))
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1))


def _filter_axes(mesh, name, dim_size: int):
    """Drop mesh axes that don't exist / don't divide dim_size."""
    if name is None:
        return None
    names = name if isinstance(name, (tuple, list)) else (name,)
    kept = []
    prod = 1
    for a in names:
        if a not in mesh.axis_names:
            continue
        sz = _axis_size(mesh, a)
        if dim_size % (prod * sz) == 0:
            kept.append(a)
            prod *= sz
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def logical_constraint(x, *axes):
    """with_sharding_constraint by logical axis names (no-op w/o rules)."""
    state = current_rules()
    if state is None:
        return x
    rules, mesh = state
    if mesh is None:
        return x
    parts = []
    for i, a in enumerate(axes):
        name = rules.get(a) if a else None
        parts.append(_filter_axes(mesh, name, x.shape[i]))
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


# ----------------------------------------------------------------------
# Parameter specs by path name
# ----------------------------------------------------------------------

# (regex on the param's dot-joined path) -> logical axes per trailing dim.
# Stacked scan params have a leading cycle dim handled separately.
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("vocab", "d_model")),
    (r"head$", ("d_model", "vocab")),
    (r"adapter_in$", ("d_model", "d_model")),
    (r"(wq|wk|wv)$", ("d_model", "heads")),     # flattened head dims
    (r"wo$", ("heads", "d_model")),
    (r"(w_gate|w_up)$", ("d_model", "ffn")),
    (r"w_down$", ("ffn", "d_model")),
    (r"router$", ("d_model", "expert")),
    (r"(moe_gate|moe_up)$", ("expert", "d_model", "ffn")),
    (r"moe_down$", ("expert", "ffn", "d_model")),
    (r"(rg_in|rg_gate)$", ("d_model", "rnn")),
    (r"rg_out$", ("rnn", "d_model")),
    (r"conv_w$", (None, "rnn")),
    (r"(lam|a_gate_w|i_gate_w)$", ("rnn",)),
    (r"(up_l|up_r)$", ("d_model", "rnn")),
    (r"(wq_i|wk_i|wv_i)$", ("rnn", "rnn")),
    (r"(wi|wf|wo_gate)$", ("rnn", "heads")),
    (r"down$", ("rnn", "d_model")),
    (r"w4$", ("d_model", "heads")),             # sLSTM fused gates
    (r"r4$", ("heads", None, None)),            # block-diag recurrent
    (r"b4$", ("heads",)),
    (r"(q_norm|k_norm|ln1|ln2|post_ln1|post_ln2|final_norm|norm)$",
     None),
]


def spec_for_path(path: str, shape: tuple, mesh, rules: dict,
                  stacked: bool, zero: bool = True) -> P:
    """PartitionSpec for one param; applies TP rules then ZeRO."""
    logical = None
    for pat, ax in _PARAM_RULES:
        if re.search(pat, path):
            logical = ax
            break
    ndim = len(shape)
    parts: list = [None] * ndim
    off = 1 if stacked else 0
    used: set = set()

    def _dedup(f):
        """Drop mesh axes already used by an earlier dim of this param."""
        if f is None:
            return None
        names = f if isinstance(f, tuple) else (f,)
        kept = tuple(a for a in names if a not in used)
        if not kept or kept != names:
            return None          # partial use would break divisibility
        used.update(kept)
        return kept if len(kept) > 1 else kept[0]

    if logical is not None:
        for i, a in enumerate(logical):
            j = off + i
            if j >= ndim or a is None:
                continue
            parts[j] = _dedup(_filter_axes(mesh, rules.get(a), shape[j]))
    if zero and int(np.prod(shape or (1,))) >= ZERO_MIN_ELEMS:
        # ZeRO only pays for big tensors; sharding a 1k-element norm
        # scale costs a per-use all-gather that XLA cannot hoist out of
        # rematerialized scan bodies (§Perf cell-1 iter-3: millions of
        # tiny in-loop all-gathers in the sLSTM step).
        zaxis = rules.get("zero")
        if zaxis is not None:
            # largest still-unsharded dim (excluding the stack dim).
            order = sorted(range(off, ndim), key=lambda i: -shape[i])
            for i in order:
                if parts[i] is None:
                    f = _dedup(_filter_axes(mesh, zaxis, shape[i]))
                    if f is not None:
                        parts[i] = f
                        break
    return P(*parts)


def param_specs(params, mesh, rules: dict | None = None, *,
                stacked_prefixes: Sequence[str] = ("cycles",),
                zero: bool = True):
    """Tree of PartitionSpec matching a params pytree, by path names."""
    rules = dict(DEFAULT_RULES if rules is None else rules)

    def leaf_spec(path_tuple, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", None))
                for p in path_tuple]
        path = ".".join(str(k) for k in keys)
        stacked = any(path.startswith(pfx) for pfx in stacked_prefixes)
        return spec_for_path(path, leaf.shape, mesh, rules, stacked, zero)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)
