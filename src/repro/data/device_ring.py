"""Device-resident FCPR ring (ROADMAP: "prefetch depth tuning + device-
resident FCPR ring").

FCPR sampling (paper §3.4) makes batch identity a pure function of the step
index — ``t = j mod n_b`` — so the whole permuted epoch can be uploaded to
device ONCE and every batch served as a ``lax.dynamic_slice`` on the ring.
That removes the per-step host→device copy (and the numpy slice feeding it)
from the hot path entirely, which is what lets the chunked trainer
(``repro.train.chunked``) run K steps per host dispatch with zero host
involvement in batch selection.

Two layouts:

  * **unsharded** (``mesh=None``): the epoch lives replicated/on the default
    device; batch t is rows ``[t*bs, (t+1)*bs)``.
  * **sharded** (``mesh`` given): the epoch is re-laid-out so each device's
    contiguous block holds *its* shard of every batch in cycle order —
    ``v.reshape(n_b, n_dev, bs/n_dev, ...)`` transposed to put the device
    axis first — then placed with ``NamedSharding(mesh, P(axes))``.  Inside
    ``shard_map`` a device slices ``[t*bs_local, (t+1)*bs_local)`` of its
    local block and gets exactly the rows the per-step engine's
    ``P(axes)``-sharded global batch would have given it, so ring and
    host-sampler feeds are bit-identical.  The relayout is keyed to the
    data *sub-axes* of the mesh, not its total size: on the hybrid
    engine's 2-D ``(data, model)`` mesh the epoch splits over the data
    sub-axis only and ``P(axes)`` replicates each block across the model
    axis — every model peer of a data shard serves identical rows.  On the
    3-D ``(pod, data, model)`` mesh the leading dim shards over
    ``("pod", "data")`` jointly, in pod-major flat order.

    ``relayout=False`` keeps the **global row order** while still
    distributing the epoch ``P(axes)`` across the mesh — the layout the
    hybrid engine's GSPMD strategy wants: its in-scan ``dynamic_slice``
    picks the *global* batch ``[t*bs, (t+1)*bs)`` and the partitioner
    re-lays it out per the step's constraints (the per-device relayout
    only exists so a *manual* shard_map body can slice its own rows).

**Multi-process striping** (ROADMAP: multi-host scale-out): when ``mesh``
spans several processes, no process holds — or uploads — the whole epoch.
The sampler still permutes the *global* epoch (every process draws the same
permutation from the same seed), but each process materializes only its
stripe: the rows of the flattened data-shard order that land on its own
devices (``repro.launch.mesh.local_data_block``), uploaded via
``jax.make_array_from_process_local_data``.  Because
``make_training_mesh`` keeps each process's devices contiguous in pod-major
flat order, the stripe is one contiguous run of shard blocks, and the union
of all stripes is exactly the single-host permuted epoch — the "one ψ
window = one epoch" invariant survives scale-out, and in-shard_map slices
still equal the single-host ``P("data")`` shards bit-for-bit (pinned by
``repro.distributed.multihost_parity``).

``ring_or_prefetch`` is the configurable-byte-budget front door: epochs
whose **per-replica share** (1/n_dev of the epoch on a sharded ring) fits
``byte_budget`` are promoted to a :class:`DeviceRing`; epochs that don't
fall back to the double-buffered ``PrefetchSampler`` — a per-step
host→device stream instead of one-shot residency.  Under a sharded mesh the
fallback changes the transfer pattern, not the values: batches are still
``P(axes)``-sharded and bit-identical, but every step pays an H2D copy and
the chunked trainer loses its zero-host-involvement property (it needs
``ring.arrays``).  On a **multi-process** mesh the fallback additionally
changes collective behaviour — per-step uploads must be coordinated across
processes every step instead of once per epoch — so the promotion failure
is warned about (once); raise ``byte_budget`` (or pass ``None``) if the
warning appears on a parity-sensitive run.

The ring preserves the sampler protocol (``__call__(j)``, ``n_batches``,
``batch_size``, ``batch_index``), so per-step engines can consume it
unchanged; chunked engines take ``ring.arrays`` directly.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BYTE_BUDGET = 256 * 1024 * 1024     # 256 MiB of epoch per replica

AxisSpec = Union[str, Tuple[str, ...], None]


def _norm_axes(mesh, axis: AxisSpec) -> tuple:
    """axis -> tuple of mesh axis names (None = the mesh's data sub-axes)."""
    if axis is None:
        from repro.launch.mesh import data_axes
        axes = data_axes(mesh)
        assert axes, f"mesh has no data axes: {tuple(mesh.shape)}"
        return axes
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _is_multiprocess(mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


def _shard_layout(v: np.ndarray, n_batches: int, n_dev: int,
                  block: Optional[tuple] = None) -> np.ndarray:
    """(n_b*bs, ...) -> rows regrouped so device d's contiguous 1/n_dev
    block is [batch0 shard d, batch1 shard d, ...].  With ``block=(lo,hi)``
    only the blocks of flat shard positions [lo, hi) are materialized —
    this process's stripe of the relayout."""
    bs = v.shape[0] // n_batches
    bsl = bs // n_dev
    lo, hi = block if block is not None else (0, n_dev)
    r = v.reshape(n_batches, n_dev, bsl, *v.shape[1:])[:, lo:hi]
    return np.ascontiguousarray(
        r.swapaxes(0, 1).reshape(n_batches * bsl * (hi - lo), *v.shape[1:]))


class DeviceRing:
    def __init__(self, epoch_arrays: Dict[str, np.ndarray], batch_size: int,
                 *, mesh=None, axis: AxisSpec = "data",
                 relayout: bool = True):
        n = next(iter(epoch_arrays.values())).shape[0]
        for v in epoch_arrays.values():
            assert v.shape[0] == n, "epoch arrays must share the leading dim"
        assert n % batch_size == 0, (n, batch_size)
        self.batch_size = batch_size
        self.n_batches = n // batch_size
        self.mesh = mesh

        if mesh is None:
            self.axis = axis
            self.n_devices = 1
            self.local_batch_size = batch_size
            self.arrays = {k: jax.device_put(np.ascontiguousarray(v))
                           for k, v in epoch_arrays.items()}
            self._slice = jax.jit(self._slice_unsharded)
            return

        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        axes = _norm_axes(mesh, axis)
        for a in axes:
            assert a in mesh.shape, \
                f"ring axis {a!r} not in mesh axes {tuple(mesh.shape)}"
        self.axis = axes[0] if len(axes) == 1 else axes
        n_dev = int(np.prod([mesh.shape[a] for a in axes]))
        assert batch_size % n_dev == 0, \
            f"batch {batch_size} not divisible by {n_dev} {axes} devices"
        self.n_devices = n_dev
        self.local_batch_size = batch_size // n_dev
        spec = P(self.axis)
        sh = NamedSharding(mesh, spec)
        multiproc = _is_multiprocess(mesh)
        if multiproc:
            from repro.launch.mesh import local_data_block
            lo, hi, total = local_data_block(mesh, axes)
            assert total == n_dev
            self.local_block = (lo, hi)
        else:
            self.local_block = (0, n_dev)

        if not relayout:
            # global row order, distributed placement (GSPMD consumers)
            if multiproc:
                rows = n // n_dev
                lo, hi = self.local_block
                self.arrays = {
                    k: jax.make_array_from_process_local_data(
                        sh, np.ascontiguousarray(
                            np.asarray(v)[lo * rows:hi * rows]), v.shape)
                    for k, v in epoch_arrays.items()}
            else:
                self.arrays = {
                    k: jax.device_put(np.ascontiguousarray(v), sh)
                    for k, v in epoch_arrays.items()}
            self._slice = jax.jit(self._slice_unsharded)
            return

        if multiproc:
            self.arrays = {
                k: jax.make_array_from_process_local_data(
                    sh, _shard_layout(np.asarray(v), self.n_batches, n_dev,
                                      self.local_block), v.shape)
                for k, v in epoch_arrays.items()}
        else:
            self.arrays = {
                k: jax.device_put(_shard_layout(np.asarray(v),
                                                self.n_batches, n_dev), sh)
                for k, v in epoch_arrays.items()}
        from jax import shard_map
        sliced = shard_map(self._slice_local, mesh=mesh,
                           in_specs=(spec, P()), out_specs=spec,
                           check_vma=False)
        self._slice = jax.jit(sliced)

    # -- slicing --------------------------------------------------------
    def _slice_unsharded(self, arrays, t):
        bs = self.batch_size
        return {k: jax.lax.dynamic_slice_in_dim(v, t * bs, bs)
                for k, v in arrays.items()}

    def _slice_local(self, arrays, t):
        bs = self.local_batch_size
        return {k: jax.lax.dynamic_slice_in_dim(v, t * bs, bs)
                for k, v in arrays.items()}

    # -- sampler protocol ----------------------------------------------
    def batch_index(self, j: int) -> int:
        return j % self.n_batches

    def __call__(self, j: int) -> Dict[str, jax.Array]:
        """Batch ``t = j mod n_b`` as device arrays — on a sharded ring the
        output is the *global* batch laid out like ``batch_sharding`` (leading
        dim over the data axes), directly consumable by the per-step
        engines.  Valid to call from every process of a multi-process mesh
        (the batch index is a python int, identical everywhere by FCPR)."""
        t = self.batch_index(j)
        if self.mesh is not None and _is_multiprocess(self.mesh):
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            t = jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P()),
                np.asarray(t, np.int32), ())
        else:
            t = jnp.asarray(t, jnp.int32)
        return self._slice(self.arrays, t)

    # -- sizing ---------------------------------------------------------
    @property
    def nbytes(self) -> int:
        """Global epoch footprint (all processes' stripes together)."""
        return sum(int(np.prod(v.shape)) * v.dtype.itemsize
                   for v in self.arrays.values())


def ring_or_prefetch(sampler, *, mesh=None, axis: AxisSpec = "data",
                     byte_budget: Optional[int] = DEFAULT_BYTE_BUDGET,
                     prefetch_depth: int = 2, relayout: bool = True):
    """Promote ``sampler``'s permuted epoch to a :class:`DeviceRing` when
    its *per-replica* share fits ``byte_budget`` bytes (``None`` = always
    fits; a sharded ring puts only 1/n_dev of the epoch on each device);
    otherwise fall back to the double-buffered ``PrefetchSampler`` over the
    same sampler, sharded for ``mesh`` if one is given.  Either return
    value satisfies the sampler protocol and yields bit-identical batches.

    Under a sharded mesh the fallback is a *transfer-pattern* change, not a
    values change: instead of one epoch upload and in-device slicing, every
    batch is a fresh host→device copy (double-buffered), and chunked-K
    consumers that need ``ring.arrays`` cannot use it.  On a
    **multi-process** mesh this additionally turns the data feed into a
    per-step cross-process coordination point, so the silent demotion is
    surfaced with a (once-per-process) ``UserWarning`` — raise
    ``byte_budget`` or pass ``byte_budget=None`` to force residency.

    The size check uses ``sampler.epoch_nbytes()`` so an over-budget epoch
    is never materialized just to be discarded."""
    if byte_budget is not None:
        if mesh is not None:
            axes = _norm_axes(mesh, axis)
            n_dev = int(np.prod([mesh.shape[a] for a in axes]))
        else:
            n_dev = 1
        if sampler.epoch_nbytes() > byte_budget * n_dev:
            if mesh is not None and _is_multiprocess(mesh):
                # keyed + coordinator-gated: fires once, on process 0 only
                from repro.obs.console import CONSOLE
                CONSOLE.warn_once(
                    "device_ring.prefetch_fallback",
                    f"epoch ({sampler.epoch_nbytes()} B) exceeds the "
                    f"device-ring byte budget ({byte_budget} B/replica x "
                    f"{n_dev}); falling back to per-step prefetch on a "
                    f"multi-process mesh — the data feed becomes a "
                    f"per-step cross-process upload instead of one "
                    f"resident epoch stripe. Raise byte_budget (or pass "
                    f"None) to keep the ring.")
            from repro.distributed.prefetch import prefetched
            return prefetched(sampler, mesh, axis=axis, depth=prefetch_depth)
    return DeviceRing(sampler.epoch_arrays(), sampler.batch_size,
                      mesh=mesh, axis=axis, relayout=relayout)
