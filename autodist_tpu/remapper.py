"""Remapper: host data -> mesh-sharded device arrays.

Parity: ``/root/reference/autodist/remapper.py:29-313`` — the reference hooks
TF's feed/fetch expansion to split the polymorphic batch dimension across
replicas (``np.array_split``, ``remapper.py:109-123``) and contract fetches
back to master-replica values.  On TPU the same job is: place each host's
batch onto the mesh with dim 0 sharded over the data axis
(``jax.make_array_from_process_local_data`` handles the multi-host case:
each process contributes its local shard of the global batch), and fetches
need no contraction — replicated outputs are read once.
"""
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec

from autodist_tpu import const, observability


def transfers_copy_host_buffer():
    """Whether device_put always COPIES host memory (vs possibly aliasing
    it).  The CPU backend's zero-copy path can alias an aligned numpy
    buffer into the device array — recycling such a staging buffer into a
    loader pool would corrupt live arrays, so buffer recycling gates on
    this (DevicePrefetcher._recycle)."""
    try:
        return jax.devices()[0].platform != "cpu"
    except Exception:  # noqa: BLE001 - uninitialized backend: be safe
        return False


def _data_dim(spec):
    """Index of the dimension a PartitionSpec places on the data axis."""
    for i, entry in enumerate(spec):
        if entry == const.MESH_AXIS_DATA or (
                isinstance(entry, tuple) and const.MESH_AXIS_DATA in entry):
            return i
    return None


class Remapper:
    """Feeds host batches onto the mesh according to a DistributedProgram."""

    def __init__(self, program):
        self._program = program
        self._mesh = program.mesh
        self._sharding_cache = {}  # (treedef, ndims) -> sharding list (hot path)
        # Resolved once, like the Runner's handle: telemetry off means no
        # telemetry call on the per-step path.
        self._obs = observability if observability.enabled() else None

    def _shardings_for(self, batch):
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        key = (treedef, tuple(np.ndim(l) for l in leaves))
        shardings = self._sharding_cache.get(key)
        if shardings is None:
            specs = jax.tree_util.tree_leaves(
                self._program.batch_specs(batch),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            shardings = [NamedSharding(self._mesh, s) for s in specs]
            self._sharding_cache[key] = shardings
        return leaves, treedef, shardings

    def _block_shardings_for(self, block):
        """Shardings for a K-stacked batch block: the leading (scan) dim is
        replicated, the remaining dims follow the per-step batch specs."""
        leaves, treedef = jax.tree_util.tree_flatten(block)
        key = ("block", treedef, tuple(np.ndim(l) for l in leaves))
        shardings = self._sharding_cache.get(key)
        if shardings is None:
            sample = jax.tree_util.tree_unflatten(treedef, [
                jax.ShapeDtypeStruct(tuple(np.shape(l))[1:],
                                     np.asarray(l).dtype
                                     if not isinstance(l, jax.Array)
                                     else l.dtype)
                for l in leaves])
            specs = jax.tree_util.tree_leaves(
                self._program.batch_specs(sample),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            shardings = [NamedSharding(self._mesh, PartitionSpec(None, *s))
                         for s in specs]
            self._sharding_cache[key] = shardings
        return leaves, treedef, shardings

    @staticmethod
    def _already_placed(leaf, sharding):
        """Whether a leaf is a live, committed jax.Array already carrying
        the target sharding — the resident-batch fast path: re-running the
        device_put tree work per step costs real host time (measured ~3%
        of a compute-light step) for what is then a pure no-op."""
        if not isinstance(leaf, jax.Array) or leaf.is_deleted():
            return False
        if not getattr(leaf, "committed", getattr(leaf, "_committed", False)):
            return False
        try:
            return leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
        except (AttributeError, TypeError):
            return leaf.sharding == sharding

    def shard_batch(self, batch):
        """Shard a (process-local) batch pytree over the data axis.

        The global batch dimension must divide evenly by the data-axis size
        (the reference splits unevenly with ``np.array_split``; XLA prefers
        equal shards — the DataLoader pads/trims to keep shapes static).
        Per-batch-structure shardings are cached: this runs every step.

        Returns as soon as the transfers are *issued* (``device_put`` is
        asynchronous); DevicePrefetcher overlaps the H2D with other work
        and blocks on the arrays just before hand-out.
        """
        obs = self._obs
        if obs is None:
            return self._shard_batch(batch)
        with obs.annotate("shard_batch"):
            return self._shard_batch(batch)

    def _shard_batch(self, batch):
        n = self._program.data_axis_size
        leaves, treedef, shardings = self._shardings_for(batch)
        if all(self._already_placed(l, s)
               for l, s in zip(leaves, shardings)):
            # Fast path: every leaf is already a committed device array with
            # the target sharding (a resident batch, or a DevicePrefetcher
            # output fed back through run()) — hand the pytree back
            # untouched, no new buffers.
            return batch

        single_process = jax.process_count() <= 1

        def put(leaf, sharding):
            arr = np.asarray(leaf)
            spec = sharding.spec
            if arr.ndim and spec and spec[0] == const.MESH_AXIS_DATA:
                total = arr.shape[0] * (jax.process_count() or 1)
                if total % n != 0:
                    raise ValueError(
                        f"global batch {total} not divisible by data-axis size {n}")
            if single_process:
                # device_put handles the sharded placement directly; the
                # process-local assembly path costs several extra host
                # copies/transfers per leaf.
                return jax.device_put(arr, sharding)
            return self._put_local_shard(arr, sharding)

        out = [put(l, s) for l, s in zip(leaves, shardings)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _put_local_shard(self, arr, sharding):
        """Assemble a global array from THIS process's local shard without
        ever materializing the global batch on any host.

        ``arr`` is the process-local slice of the global value (dim 0 is
        ``1/process_count`` of the global batch for data-sharded leaves;
        the full value for replicated leaves).  Each addressable device
        gets its slice of the LOCAL array via ``device_put``, and
        ``make_array_from_single_device_arrays`` stitches the global
        array from the per-device shards — strictly less host work than
        ``make_array_from_process_local_data`` (which routes through an
        extra local-array assembly) and zero-copy friendly: the per-device
        slices are views into the staging buffer.
        """
        n_proc = jax.process_count() or 1
        spec = sharding.spec
        # The data-sharded dimension is dim 0 for per-step batches and dim 1
        # for K-stacked megastep blocks (the leading scan dim replicates).
        dim = _data_dim(spec) if arr.ndim else None
        data_sharded = dim is not None and arr.ndim > dim
        rows_scale = n_proc if data_sharded else 1
        if arr.ndim and data_sharded:
            global_shape = (arr.shape[:dim] + (arr.shape[dim] * rows_scale,)
                            + arr.shape[dim + 1:])
        else:
            global_shape = arr.shape
        idx_map = sharding.addressable_devices_indices_map(global_shape)
        if not data_sharded:
            # Replicated (or non-data-sharded) leaf: every process holds
            # the full value; each addressable device takes its own slice.
            arrays = [jax.device_put(arr[idx], d)
                      for d, idx in idx_map.items()]
            return jax.make_array_from_single_device_arrays(
                global_shape, sharding, arrays)
        # Shift the devices' GLOBAL data-dim slices into local coordinates:
        # this process's rows cover [offset, offset + arr.shape[dim]).
        starts = [(idx[dim].start or 0) for idx in idx_map.values()]
        offset = min(starts)
        arrays = []
        for d, idx in idx_map.items():
            lo = (idx[dim].start or 0) - offset
            hi = (global_shape[dim] if idx[dim].stop is None
                  else idx[dim].stop) - offset
            if not 0 <= lo <= hi <= arr.shape[dim]:
                raise ValueError(
                    f"local batch of {arr.shape[dim]} rows does not cover "
                    f"this process's device shard [{lo}, {hi}); expected "
                    f"the per-process slice of a {global_shape[dim]}-row "
                    f"global batch across {n_proc} processes")
            arrays.append(jax.device_put(
                arr[idx[:dim] + (slice(lo, hi),) + idx[dim + 1:]], d))
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, arrays)

    def shard_block(self, block):
        """Shard a K-stacked batch block (leaf shapes ``(K,) + batch``).

        Feeds the Runner's fused multi-step ("megastep") dispatch: the
        leading dim is the on-device ``lax.scan`` axis and stays
        replicated; the remaining dims carry the per-step batch sharding
        (dim 1 over ``data``).  Same fast path, caching, and asynchronous
        return as :meth:`shard_batch`.
        """
        n = self._program.data_axis_size
        leaves, treedef, shardings = self._block_shardings_for(block)
        if all(self._already_placed(l, s)
               for l, s in zip(leaves, shardings)):
            return block

        single_process = jax.process_count() <= 1

        def put(leaf, sharding):
            arr = np.asarray(leaf)
            spec = sharding.spec
            if arr.ndim > 1 and len(spec) > 1 and \
                    spec[1] == const.MESH_AXIS_DATA:
                total = arr.shape[1] * (jax.process_count() or 1)
                if total % n != 0:
                    raise ValueError(
                        f"global batch {total} not divisible by data-axis "
                        f"size {n}")
            if single_process:
                return jax.device_put(arr, sharding)
            return self._put_local_shard(arr, sharding)

        out = [put(l, s) for l, s in zip(leaves, shardings)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def shard_local_batch(self, batch):
        """Per-host feeding: ``batch`` is this process's LOCAL shard (its
        stripe of the global batch, e.g. from a ``per_host=True``
        NativeDataLoader); returns the same global device arrays
        :meth:`shard_batch` would, assembled from per-device local pieces
        so no host ever holds or ships the full global batch.  On a
        single process this is identical to :meth:`shard_batch` (the
        local shard IS the global batch)."""
        leaves, treedef, shardings = self._shardings_for(batch)
        out = [self._put_local_shard(np.asarray(l), s)
               for l, s in zip(leaves, shardings)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def place_params(self, params, shardings=None):
        """Place a parameter pytree on the mesh per the program's param
        shardings — the serve path's one-time placement: parameters are
        put ONCE and never donated (every inference dispatch reads the
        same buffers; contrast the training step, which donates state).

        ``shardings`` overrides the plan (a sharding pytree congruent
        with ``params``); default is the program's ``param_shardings()``.
        """
        if shardings is None:
            shardings = self._program.param_shardings()
        return jax.device_put(params, shardings)

    def fetch(self, value):
        """Bring a (possibly replicated/sharded) result to the host.

        Parity with fetch contraction (``remapper.py:125-185``): replicated
        outputs are read once; sharded outputs are gathered.
        """
        return jax.device_get(value)
