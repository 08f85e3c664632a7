"""Cluster runtime: JAX distributed bootstrap + device-mesh construction.

Replaces the reference's process fabric — per-node gRPC ``tf.train.Server``
processes launched over SSH (``/root/reference/autodist/cluster.py:160-210``,
``utils/server_starter.py:48-75``) — with the TPU-native model: one SPMD
process per host joined through the JAX coordination service, and a
``jax.sharding.Mesh`` laid out over ICI as the communication substrate.

The mesh is the single source of truth for collectives: strategies compile to
``PartitionSpec``s over its named axes and XLA lowers them to ICI/DCN
collectives (psum / all_gather / reduce_scatter / ppermute).
"""
import math

import numpy as np
import jax
from jax.sharding import Mesh

from autodist_tpu import const, observability
from autodist_tpu.utils import logging


class Cluster:
    """Owns distributed initialization and mesh construction for a ResourceSpec."""

    def __init__(self, resource_spec):
        self._resource_spec = resource_spec
        self._started = False
        self._mesh = None

    @property
    def resource_spec(self):
        return self._resource_spec

    def start(self):
        """Join (or create) the coordination service for multi-host runs.

        Parity point: ``Cluster.start`` in the reference boots a TF server on
        every node (``cluster.py:160-210``); here multi-host wiring is a single
        ``jax.distributed.initialize`` per host process — there are no
        per-node graph servers in an SPMD program.
        """
        if self._started:
            return
        spec = self._resource_spec
        # Decide from the spec/env contract alone: jax.process_count() would
        # initialize the backend, which must not happen before distributed
        # init on multi-host jobs.
        if spec.num_processes > 1:
            from autodist_tpu.resilience.retry import (retry_call,
                                                       transient_runtime_error)
            coordinator = spec.coordinator or \
                f"{spec.chief_address}:{const.DEFAULT_COORDINATOR_PORT}"
            logging.info("Initializing JAX distributed: coordinator=%s process=%d/%d",
                         coordinator, const.ENV.AUTODIST_PROCESS_ID.val, spec.num_processes)
            try:
                # The join races worker spawn and chief startup: connection
                # refused / deadline errors are the normal transient case
                # (a restarted worker dialing a chief that is still coming
                # up), so the join retries with backoff instead of dying
                # on the first RPC flake.
                with observability.span("distributed-init",
                                        coordinator=coordinator):
                    retry_call(
                        jax.distributed.initialize,
                        coordinator_address=coordinator,
                        num_processes=spec.num_processes,
                        process_id=const.ENV.AUTODIST_PROCESS_ID.val,
                        is_retryable=transient_runtime_error,
                        describe="jax.distributed.initialize")
            except RuntimeError as e:
                if "already" not in str(e):
                    raise
                logging.debug("jax.distributed already initialized: %s", e)
            try:
                # First clock-offset exchange as soon as the KV store is
                # up (re-run on every cluster-sync cadence): per-host
                # offset + uncertainty vs the chief, so dispatch windows
                # and traces are alignable (docs/observability.md).
                from autodist_tpu.observability import skew
                skew.maybe_sync_clocks()
            except Exception as e:  # noqa: BLE001 - telemetry must never kill init
                logging.debug("clock sync at init skipped: %s", e)
        self._started = True

    def is_chief(self):
        return jax.process_index() == 0

    # -- mesh construction ---------------------------------------------------

    def build_mesh(self, axis_sizes=None, devices=None):
        """Build a named device mesh over the cluster's accelerator devices.

        Args:
            axis_sizes: ordered dict-like {axis_name: size}. Sizes must multiply
                to <= device count; a single ``-1`` size is inferred. Defaults
                to the resource spec's ``mesh:`` hints, else all devices on the
                data axis.
            devices: explicit device list overriding ``jax.devices()`` — used
                for AOT compilation against a detached TPU topology
                (``jax.experimental.topologies``): programs lower and compile
                for the full pod shape without the chips being attached.

        The axis order follows `const.ALL_MESH_AXES` convention: innermost
        (fastest-varying, best ICI locality) axes last, so `model` / `seq`
        collectives ride neighboring chips while `data` spans the slower
        dimension — the standard recipe for keeping tensor/sequence
        collectives on ICI and gradient reductions amortized.
        """
        devices = np.array(jax.devices() if devices is None else list(devices))
        n = devices.size
        if axis_sizes is None or not axis_sizes:
            axis_sizes = dict(self._resource_spec.mesh_hints) or {const.MESH_AXIS_DATA: n}
        axis_sizes = dict(axis_sizes)

        # Infer a single -1 axis.
        known = [s for s in axis_sizes.values() if s != -1]
        prod = math.prod(known) if known else 1
        if any(s == -1 for s in axis_sizes.values()):
            if n % prod != 0:
                raise ValueError(f"Cannot infer mesh axis: {n} devices not divisible by {prod}")
            inferred = n // prod
            axis_sizes = {k: (inferred if v == -1 else v) for k, v in axis_sizes.items()}
        total = math.prod(axis_sizes.values())
        if total > n:
            raise ValueError(f"Mesh {axis_sizes} needs {total} devices, have {n}")
        if total < n:
            # Fold leftover devices into the data axis (create it if absent).
            if n % total != 0:
                raise ValueError(f"Mesh {axis_sizes} does not divide device count {n}")
            axis_sizes.setdefault(const.MESH_AXIS_DATA, 1)
            axis_sizes[const.MESH_AXIS_DATA] *= n // total

        # Canonical ordering: data outermost, then pipe/expert/seq/model innermost.
        order = {const.MESH_AXIS_DATA: 0, const.MESH_AXIS_PIPELINE: 1,
                 const.MESH_AXIS_EXPERT: 2, const.MESH_AXIS_SEQ: 3,
                 const.MESH_AXIS_MODEL: 4}
        names = sorted(axis_sizes, key=lambda a: order.get(a, 99))
        shape = tuple(axis_sizes[a] for a in names)
        if devices.flat[0].platform == "cpu":
            # Forced-host CPU devices carry no topology: plain order.
            mesh_devices = devices.reshape(shape)
        else:
            # Topology-aware layout (respects the ICI torus); a failure
            # here is a wrong mesh request and raises.
            from jax.experimental import mesh_utils
            flat = devices.flatten().tolist()
            n_slices = len({getattr(d, "slice_index", 0) for d in flat})
            if n_slices == 1:
                mesh_devices = mesh_utils.create_device_mesh(
                    shape, devices=flat)
            elif shape[0] % n_slices:
                raise ValueError(f"Mesh {dict(zip(names, shape))}: the "
                                 f"outermost axis does not divide over "
                                 f"{n_slices} slices")
            else:
                # Slices joined by DCN: the outermost axis (data) spans
                # them, every other axis stays on one slice's ICI.
                mesh_devices = mesh_utils.create_hybrid_device_mesh(
                    (shape[0] // n_slices,) + shape[1:],
                    (n_slices,) + (1,) * (len(shape) - 1), devices=flat)
        self._mesh = Mesh(mesh_devices, axis_names=tuple(names))
        logging.info("Built mesh %s over %d devices", dict(zip(names, shape)), n)
        observability.record_event(
            "mesh-built", f"{dict(zip(names, shape))} over {n} devices")
        if observability.enabled():
            # World-size gauge (elasticity trail): an elastic re-form is
            # visible as this gauge changing between incarnations'
            # telemetry snapshots (docs/elasticity.md).
            try:
                observability.registry().gauge("cluster.world_size").set(
                    jax.process_count())
            except Exception:  # noqa: BLE001 - backend quirks must not kill mesh build
                pass
        return self._mesh

    def build_hierarchical_mesh(self, devices=None, devices_per_host=None):
        """Build a nested ``(dcn, ici)`` mesh splitting the data axis by host.

        The outer ``dcn`` axis spans hosts (slow cross-host leg), the inner
        ``ici`` axis spans the devices within a host (fast leg), so the
        two-level collectives in ``kernel/synchronization/hierarchical.py``
        can be expressed directly over named axes
        (:func:`hierarchical.hier_mean_nested`).  Device order is host-major
        (``jax.devices()`` contract), so row h of the mesh is exactly host
        h's devices.  ``devices_per_host`` defaults to the resource spec's
        (``AUTODIST_HIER_ICI`` still overrides, matching the execution-side
        leg split); a split that doesn't divide the device count degenerates
        to ``dcn=1`` — the flat topology as a 1 x N mesh.
        """
        from autodist_tpu.kernel.synchronization.hierarchical import resolve_legs
        devices = np.array(jax.devices() if devices is None else list(devices))
        n = devices.size
        if devices_per_host is None:
            devices_per_host = self._resource_spec.devices_per_host
        d, h = resolve_legs(n, devices_per_host)
        mesh = Mesh(devices.flatten().reshape(h, d),
                    axis_names=(const.MESH_AXIS_DCN, const.MESH_AXIS_ICI))
        logging.info("Built hierarchical mesh {%s: %d, %s: %d}",
                     const.MESH_AXIS_DCN, h, const.MESH_AXIS_ICI, d)
        return mesh

    @property
    def mesh(self):
        if self._mesh is None:
            self.build_mesh()
        return self._mesh

    def terminate(self):
        """Tear down distributed state (parity: ``Cluster.terminate``)."""
        self._started = False
