"""Production mesh construction (a function — importing this module
never touches jax device state).

Axes:
    pod   — FL clients (FLTorrent dissemination axis; DP-outer)
    data  — within-client data parallel + ZeRO/FSDP shard axis
    model — tensor / expert parallel axis

Scaling story (DESIGN.md §5): capacity grows by adding pods (clients),
which is the paper's own scaling dimension (Table III shows flat
warm-up share from 100 to 500 peers) — ``n_pods`` is a parameter, not a
constant, and every collective in the torrent schedule is written for
general P.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False, n_pods: int = 2):
    shape = (n_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                     axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many devices this host has (tests)."""
    return jax.make_mesh(shape, axes,
                     axis_types=(AxisType.Auto,) * len(axes))


def make_pod_mesh(n_pods: int, *, data: int = 1, model: int = 1,
                  devices=None):
    """("pod", "data", "model") mesh over a *subset* of the host's
    devices — the elastic re-mesh entry point (§III-E).

    Dropping from P to P-1 pods keeps the first ``(P-1)*data*model``
    devices and rebuilds the mesh; the torrent ring schedule then
    re-lowers for the new pod axis automatically (its stage count is
    P-1).  ``devices`` overrides the host device list (tests).
    """
    need = n_pods * data * model
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) < need:
        raise ValueError(f"{need} devices needed for pods={n_pods} x "
                         f"data={data} x model={model}; have {len(devs)}")
    return jax.make_mesh((n_pods, data, model), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3,
                     devices=devs[:need])


def pod_axis_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("pod", 1))
