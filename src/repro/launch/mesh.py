"""Process-aware training meshes: one factory from the single-host debug
mesh to the multi-pod production grid.

A function, not a module-level constant — importing this module must never
touch jax device state (the dry-run and the launchers set XLA_FLAGS and
``jax.distributed.initialize`` before first init; see ``repro.launch.env``).

:func:`make_training_mesh` is the single factory.  It builds over the
**global** device set (every process's devices, ordered process-major) and
produces

  * ``(data, model)`` when the pod axis is trivial — the historical
    single-host hybrid mesh, byte-compatible with what
    ``make_host_mesh`` always returned;
  * ``(pod, data, model)`` when ``pod > 1`` — one pod row per process by
    default (``pod = jax.process_count()``), so the flattened
    ``("pod", "data")`` order walks process 0's devices first, then
    process 1's, …  That ordering is load-bearing: the FCPR data layer
    stripes the permuted epoch by process index against exactly this
    flat order (``repro.data.device_ring``), and ψ/grad reduction over
    ``("pod", "data")`` in flat shard order reproduces the single-host
    ``("data",)`` reduction bit-exactly (``core/reduce.py``,
    ``AxisReduce(deterministic=True)``).

Validation failures raise :class:`MeshError` (a ``ValueError``) — library
code never calls ``SystemExit``; the CLI boundary in ``launch/train.py``
translates.  ``make_host_mesh``/``make_data_mesh``/``make_production_mesh``
remain as thin views of the factory for their existing callers.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


class MeshError(ValueError):
    """A requested mesh shape cannot be built from the available devices
    (non-divisible axis sizes, or a device order that breaks the
    process-striping contract)."""


def _make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned): the
    engines place and constrain shardings themselves."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def global_device_order(devices=None) -> list:
    """The canonical global device order: process-major, then id — the
    order the pod axis, the FCPR stripes, and the deterministic reduction
    all key on."""
    devs = list(devices) if devices is not None else jax.devices()
    return sorted(devs, key=lambda d: (d.process_index, d.id))


def data_axes(mesh) -> tuple:
    """The data sub-axes of a training mesh, in reduction (pod-major flat)
    order — what ``AxisReduce``/``P`` specs should span for ψ/grad
    reduction and batch sharding.  ``("pod", "data")`` on a 3-D mesh,
    ``("data",)`` otherwise."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def is_multiprocess(mesh) -> bool:
    """True when the mesh spans devices of more than one process."""
    procs = {d.process_index for d in mesh.devices.flat}
    return len(procs) > 1


def _check_pod_rows(mesh) -> None:
    """Multi-process meshes must keep each process's devices contiguous
    along the flattened ``(pod, data)`` order, or the data layer's
    process striping would interleave rows across hosts."""
    if not is_multiprocess(mesh):
        return
    rows = mesh.devices.reshape(-1, mesh.shape["model"])
    procs = [rows[i, 0].process_index for i in range(rows.shape[0])]
    for i in range(1, len(procs)):
        if procs[i] < procs[i - 1]:
            raise MeshError(
                f"mesh devices are not process-contiguous along the "
                f"flattened (pod, data) order (process sequence {procs}); "
                f"the FCPR striping contract needs process p's devices in "
                f"one contiguous block — build the mesh through "
                f"make_training_mesh over global_device_order()")


def make_training_mesh(model: int = 1, *, pod: Optional[int] = None,
                       devices=None):
    """THE mesh factory: ``(pod, data, model)`` over the global device set.

    ``model`` devices go to the tensor-parallel axis; ``pod`` (default: the
    process count, so one pod per host process) splits the remainder's
    outer dim; what's left is ``data``.  ``pod == 1`` drops the pod axis
    and returns the historical 2-D ``(data, model)`` mesh so single-host
    callers (and their compiled-program caches) see exactly what
    ``make_host_mesh`` always built.  An explicit ``devices`` list pins a
    sub-mesh (parity tests build ``(1, 1)`` meshes on multi-device
    processes).

    Raises :class:`MeshError` on non-divisible shapes — library callers
    get a ``ValueError`` they can handle; only the CLI translates it to an
    exit code.
    """
    devs = global_device_order(devices)
    n = len(devs)
    if model < 1 or n % model:
        raise MeshError(
            f"model-parallel degree must divide the device count: "
            f"n={n} devices, M={model} (choose M from the divisors of {n})")
    if pod is None:
        pod = len({d.process_index for d in devs})
    if pod < 1 or n % (pod * model):
        raise MeshError(
            f"pod axis must divide the non-model device count: n={n} "
            f"devices, pod={pod}, M={model} (n must be a multiple of "
            f"pod*M={pod * model})")
    if pod == 1:
        return _make_mesh((n // model, model), ("data", "model"),
                          devices=devs)
    mesh = _make_mesh((pod, n // (pod * model), model),
                      ("pod", "data", "model"), devices=devs)
    _check_pod_rows(mesh)
    return mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e-256).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips across DCI."""
    return make_training_mesh(model=16, pod=2 if multi_pod else 1)


def make_host_mesh(model: int = 1, devices=None):
    """2-D ``(data, model)`` mesh over however many (CPU) devices exist —
    the hybrid DP × TP engine's single-host debug mesh.  ``model`` of the
    devices go to the tensor-parallel axis; the rest form the data axis.
    An explicit ``devices`` list pins a sub-mesh (parity tests use it to
    build a ``(1, 1)`` mesh on a multi-device process).  Raises
    :class:`MeshError` when ``model`` doesn't divide the device count."""
    return make_training_mesh(model=model, pod=1, devices=devices)


def make_data_mesh(devices=None):
    """1-axis ('data',) mesh for the pure data-parallel ISGD engine
    (repro.distributed): params/state replicated, batch sharded.  Uses every
    device unless an explicit list is given."""
    n = len(devices) if devices is not None else len(jax.devices())
    return _make_mesh((n,), ("data",), devices=devices)


def local_data_block(mesh, axis=None) -> tuple:
    """This process's contiguous block ``(lo, hi, total)`` of flattened
    data-shard positions on ``mesh`` — the index range the FCPR data layer
    stripes the global epoch by (``repro.data.device_ring``).

    ``axis`` defaults to :func:`data_axes`.  On a single-process mesh the
    block is ``(0, total, total)``.  Raises :class:`MeshError` when this
    process's devices do not form one contiguous run (the striping
    contract; meshes from :func:`make_training_mesh` always satisfy it).
    """
    axes = data_axes(mesh) if axis is None else (
        (axis,) if isinstance(axis, str) else tuple(axis))
    # flatten device grid to (flat_data, model): move data axes first, in
    # pod-major order, then everything else
    names = list(mesh.axis_names)
    order = [names.index(a) for a in axes] + [
        i for i, a in enumerate(names) if a not in axes]
    grid = np.transpose(mesh.devices, order)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    flat = grid.reshape(total, -1)
    pid = jax.process_index()
    mine = [i for i in range(total)
            if flat[i, 0].process_index == pid]
    if not mine:
        raise MeshError(f"process {pid} owns no devices on this mesh")
    lo, hi = mine[0], mine[-1] + 1
    if mine != list(range(lo, hi)):
        raise MeshError(
            f"process {pid}'s data-shard positions {mine} are not "
            f"contiguous; build the mesh through make_training_mesh")
    return lo, hi, total
