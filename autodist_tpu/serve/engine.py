"""AOT bucket compiler + per-replica inference runtimes.

The serving engine reuses the training stack end to end — capture
(:meth:`GraphItem.capture` on the forward-only ``apply_fn``), strategy
(any :class:`StrategyBuilder`, or the tuner under its
``serve_latency`` objective), compile (:class:`StrategyCompiler`),
transform (:class:`GraphTransformer` -> :class:`DistributedProgram`) —
but inverts the execution contract:

* parameters are placed ONCE per replica (``Remapper.place_params``)
  and **never donated**: every dispatch reads the same buffers, so two
  identical requests are bitwise-identical answers;
* the step function is AOT-compiled at a small set of padded batch
  *buckets* (``serve/buckets.py``) — no shape-polymorphic jit cache
  growth, no compile on the request path;
* uneven param shardings reuse the training pad-and-mask plan
  (``DistributedProgram.paddings()``): storage is padded, the compiled
  forward slices the logical region before the user program runs.

Multi-replica: when the mesh holds R independent model replicas (only
legal for strategies whose non-data mesh axes are trivial — params
replicate, so each device group can hold a full copy), the device list
is carved into R contiguous groups, each with its own data-axis mesh,
program, placed params, and AOT executables.  Each replica runs one
executor thread fed through the depth-N :class:`DevicePrefetcher`
(lazy top-up: the window fills opportunistically from queued work, so
an idle queue never stalls a latency-sensitive dispatch) — host->device
transfer of the next bucket overlaps the current execute exactly as in
training.
"""
import queue
import threading
import time
import types

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from autodist_tpu import const, observability
from autodist_tpu.cluster import Cluster
from autodist_tpu.data.loader import DevicePrefetcher
from autodist_tpu.graph_item import GraphItem, path_to_name
from autodist_tpu.kernel.graph_transformer import GraphTransformer
from autodist_tpu.remapper import Remapper
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.serve.buckets import normalize_buckets
from autodist_tpu.strategy.base import StrategyCompiler
from autodist_tpu.utils import logging


def _oom_forensics(exc, context):
    """Serve-side OOM hook: when an AOT compile or a dispatch dies with a
    device allocation failure, emit the forensics report
    (``logs/oom_report.json`` + the ``oom`` flight event) before the
    caller re-raises / fails the request futures.  Fail-open — forensics
    must never mask the original error."""
    try:
        from autodist_tpu.observability import memory as memory_mod
        if memory_mod.is_oom(exc):
            memory_mod.oom_report(exc, context=context)
    except Exception as e:  # noqa: BLE001 - diagnostics only
        logging.debug("serve oom forensics failed: %s", e)


def build_replica_programs(item, strategy, spec, replicas):
    """One DistributedProgram per replica.  R=1 uses the full mesh
    (any GSPMD sharding the strategy asks for); R>1 carves the device
    list into R contiguous data-only groups, which is only legal when
    the strategy keeps params whole per device group.  Shared by the
    one-shot :class:`ServeEngine` and the autoregressive
    :class:`~autodist_tpu.serve.decode.DecodeEngine` (whose autoscaler
    re-carves at every scale event)."""
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")

    def transform(mesh):
        compiled = StrategyCompiler(item, mesh).compile(strategy)
        # resource_spec rides along so synchronizers resolve the
        # ICI/DCN leg split (devices_per_host) for per-leg wire gauges.
        holder = types.SimpleNamespace(mesh=mesh, resource_spec=spec)
        return GraphTransformer(compiled, holder, item).transform()

    axes = dict(strategy.graph_config.mesh_axes)
    if replicas == 1:
        cluster = Cluster(spec)
        mesh = cluster.build_mesh(axes or None)
        yield transform(mesh)
        return
    nondata = {a: k for a, k in axes.items()
               if a != const.MESH_AXIS_DATA and k > 1}
    if nondata:
        raise ValueError(
            f"multi-replica dispatch needs a data-only strategy "
            f"(params whole per replica); this one carves mesh axes "
            f"{nondata} — serve it with replicas=1")
    devices = jax.devices()
    if len(devices) % replicas:
        raise ValueError(
            f"{len(devices)} devices do not split into {replicas} "
            f"equal replicas")
    per = len(devices) // replicas
    for i in range(replicas):
        group = np.array(devices[i * per:(i + 1) * per])
        mesh = Mesh(group, (const.MESH_AXIS_DATA,))
        yield transform(mesh)


def _resolve_serve_builder(builder):
    """Serving strategy policy: an explicit builder wins; else
    ``AUTODIST_STRATEGY`` ('auto' => the tuner under the
    ``serve_latency`` objective); else AllReduce (fully replicated
    params — the canonical serving layout)."""
    if builder is not None:
        return builder
    name = const.ENV.AUTODIST_STRATEGY.val
    if name:
        if str(name).strip().lower() in ("auto", "autostrategy"):
            from autodist_tpu.tuner import AutoStrategy
            return AutoStrategy(objective="serve_latency")
        from autodist_tpu.tuner import builder_from_name
        return builder_from_name(name)
    from autodist_tpu.strategy.all_reduce_strategy import AllReduce
    return AllReduce()


class _WorkQueue:
    """Replica work source: a queue that speaks both the blocking
    iterator protocol (the DevicePrefetcher's pop) and ``next_nowait``
    (its lazy top-up)."""

    _STOP = object()

    def __init__(self):
        self._q = queue.Queue()

    def put(self, item):
        self._q.put(item)

    def close(self):
        self._q.put(self._STOP)

    def qsize(self):
        return self._q.qsize()

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._STOP:
            raise StopIteration
        return item

    def next_nowait(self):
        try:
            item = self._q.get_nowait()
        except queue.Empty:
            return None
        if item is self._STOP:
            raise StopIteration
        return item


class ReplicaRuntime:
    """One model replica: a mesh slice, resident (never-donated) params,
    and AOT executables for every bucket."""

    def __init__(self, index, program, apply_fn, obs=None):
        self.index = index
        self.program = program
        self.remapper = Remapper(program)
        self._apply = apply_fn
        self._paddings = program.paddings()
        self._obs = obs
        self._fns = {}  # bucket tuple -> AOT executable
        self._bucket_rank = 1
        self._source = None
        self._thread = None
        self._on_complete = None
        self._lock = threading.Lock()
        self._removed = False      # mid-flight removal: drain, don't run
        self._drained = []         # queued items skipped after removal
        self.outstanding = 0       # dispatched, not yet completed
        self.dispatches = 0
        self._busy_s = 0.0
        self._started_at = time.perf_counter()
        self.params = self.remapper.place_params(self._pad_params(
            program.graph_item.params))

    # -- pad-and-mask (reuses the training plan) -----------------------------

    def _pad_params(self, params):
        if not self._paddings:
            return params
        def pad(path, x):
            plan = self._paddings.get(path_to_name(path))
            if plan is None:
                return x
            dim, logical, padded = plan
            widths = [(0, padded - logical if i == dim else 0)
                      for i in range(np.ndim(x))]
            return np.pad(np.asarray(x), widths)
        return jax.tree_util.tree_map_with_path(pad, params)

    def _unpad_params(self, params):
        if not self._paddings:
            return params
        def unpad(path, x):
            plan = self._paddings.get(path_to_name(path))
            if plan is None:
                return x
            dim, logical, _ = plan
            return jax.lax.slice_in_dim(x, 0, logical, axis=dim)
        return jax.tree_util.tree_map_with_path(unpad, params)

    # -- AOT bucket compiler -------------------------------------------------

    def _serve_fn(self):
        apply_fn = self._apply

        def fn(params, batch):
            return apply_fn(self._unpad_params(params), batch)
        return fn

    def compile_bucket(self, bucket, batch_struct):
        """AOT-compile the forward at one padded bucket.  ``bucket`` is
        an int (batch rows) or a tuple of leading dims — ``(rows, seq)``
        buckets pad both the batch and the sequence dimension of every
        leaf (docs/serving.md).  Params are NOT in ``donate_argnums``:
        the executable may never free them."""
        bucket = (int(bucket),) if not isinstance(bucket, (tuple, list)) \
            else tuple(int(x) for x in bucket)
        if bucket in self._fns:
            return self._fns[bucket]
        rows = bucket[0]
        n = self.program.data_axis_size
        if rows % n:
            raise ValueError(
                f"serve bucket {rows} not divisible by this replica's "
                f"data-axis size {n}; pick bucket sizes that are "
                f"multiples of the per-replica device count")
        rank = len(bucket)
        for s in jax.tree_util.tree_leaves(batch_struct):
            if len(s.shape) < rank:
                raise ValueError(
                    f"bucket {bucket} pads {rank} leading dims but a "
                    f"batch leaf has shape {tuple(s.shape)} (rank "
                    f"{len(s.shape)}); use batch-only buckets for this "
                    f"model")
        struct = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(bucket + tuple(s.shape)[rank:],
                                           s.dtype), batch_struct)
        mesh = self.program.mesh
        batch_sh = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            self.program.batch_specs(struct),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        param_sh = self.program.param_shardings()
        obs = self._obs
        t0 = time.perf_counter()
        with (obs.span("serve-aot-compile", bucket=str(bucket),
                       replica=self.index) if obs is not None
              else observability.tracing.NULL_SPAN):
            fn = jax.jit(self._serve_fn(),
                         in_shardings=(param_sh, batch_sh)) \
                .lower(self.params, struct).compile()
        dt_ms = (time.perf_counter() - t0) * 1e3
        logging.info("serve: replica %d compiled bucket %s (%.0fms)",
                     self.index, bucket, dt_ms)
        if obs is not None:
            obs.registry().gauge("serve.aot_compile.ms").set(round(dt_ms, 3))
            obs.record_event("serve-compile",
                             f"replica {self.index} bucket {bucket} "
                             f"({dt_ms:.0f}ms)")
            self._record_wire_split(obs)
        self._bucket_rank = rank
        self._fns[bucket] = fn
        return fn

    def _record_wire_split(self, obs):
        """Per-leg wire gauges for this replica's per-dispatch parameter
        all-gathers (data-sharded storage re-materialized on every
        request): ``comms.wire_ici_bytes`` / ``comms.wire_dcn_bytes``,
        the serving-side mirror of the training runner's split
        (docs/collectives.md).  Fail-open."""
        try:
            from autodist_tpu.kernel.synchronization import hierarchical
            sizes = {v.name: v.size_bytes
                     for v in self.program.graph_item.variables}
            split = hierarchical.gather_wire_split(
                self.program.synchronizers, sizes,
                self.program.data_axis_size)
            obs.registry().gauge("comms.wire_ici_bytes").set(
                round(split["ici"], 1))
            obs.registry().gauge("comms.wire_dcn_bytes").set(
                round(split["dcn"], 1))
        except Exception as e:  # noqa: BLE001 - telemetry only
            logging.debug("serve wire split skipped: %s", e)

    @property
    def buckets_compiled(self):
        """Compiled buckets, ints for batch-only buckets (back-compat),
        tuples for multi-dim ones."""
        return sorted(b[0] if len(b) == 1 else b for b in self._fns)

    # -- dispatch loop -------------------------------------------------------

    def _shard_item(self, item):
        batch, group, rows = item
        return (self.remapper.shard_batch(batch), group, rows)

    def start(self, on_complete, depth=None):
        """Spin up the executor thread behind a depth-N prefetch window."""
        self._on_complete = on_complete
        self._source = _WorkQueue()
        self._prefetch = DevicePrefetcher(
            self._source, self.remapper, depth=depth,
            shard_fn=self._shard_item, pull_in_background=False)
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"autodist-serve-replica-{self.index}")
        self._thread.start()

    def enqueue(self, batch, group, rows):
        with self._lock:
            self.outstanding += 1
        self._source.put((batch, group, rows))

    def _loop(self):
        while True:
            try:
                db, group, rows = next(self._prefetch)
            except StopIteration:
                break
            except Exception as e:  # noqa: BLE001 - surface on the futures
                self._fail_all(e)
                continue
            if self._removed:
                # Forced mid-flight removal: queued work is never run
                # here — it drains back to the engine for re-dispatch on
                # a surviving replica (no future fails, no request drops).
                self._drained.append((db, group, rows))
                with self._lock:
                    self.outstanding -= 1
                continue
            t0 = time.perf_counter()
            try:
                shape = jax.tree_util.tree_leaves(db)[0].shape
                bucket = tuple(int(d) for d in shape[:self._bucket_rank])
                out = self._fns[bucket](self.params, db)
                host = jax.device_get(out)
            except Exception as e:  # noqa: BLE001 - per-batch failure
                _oom_forensics(e, f"serve dispatch replica {self.index}")
                for r in group:
                    if not r.future.done():
                        r.future.set_exception(e)
                with self._lock:
                    self.outstanding -= 1
                continue
            self._busy_s += time.perf_counter() - t0
            with self._lock:
                self.outstanding -= 1
                self.dispatches += 1
            self._on_complete(self, group, host, rows)

    def _fail_all(self, exc):
        """A sharding/transfer fault poisons whatever is queued; drain it."""
        while True:
            item = self._source.next_nowait()
            if item is None:
                break
            for r in item[1]:
                if not r.future.done():
                    r.future.set_exception(exc)
            with self._lock:
                self.outstanding -= 1

    def drain_close(self):
        """Stop this replica WITHOUT running or failing its queued work:
        the in-flight dispatch (if any) completes normally, everything
        still queued comes back as ``(batch, group, rows)`` items for
        re-dispatch elsewhere (``ServeEngine.remove_replica``)."""
        self._removed = True
        if self._source is not None:
            self._source.close()
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        drained, self._drained = self._drained, []
        return drained

    @property
    def utilization(self):
        """Fraction of wall time this replica spent executing."""
        dt = time.perf_counter() - self._started_at
        return self._busy_s / dt if dt > 0 else 0.0

    def close(self):
        if self._source is not None:
            self._source.close()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None


class ServeEngine:
    """capture -> strategy -> per-replica (mesh, program, params, AOT
    bucket executables).  The :class:`~autodist_tpu.serve.server.Server`
    owns the request queue in front of this."""

    def __init__(self, apply_fn, params, example_batch, buckets,
                 resource_spec=None, strategy_builder=None, replicas=1):
        if example_batch is None:
            raise ValueError("serve needs an example_batch: bucket "
                             "compilation specializes on its structure "
                             "(trailing dims + dtypes)")
        self.buckets = normalize_buckets(buckets)
        self.bucket_rank = len(self.buckets[0])
        if self.bucket_rank > 2:
            raise ValueError(
                f"serve buckets pad at most (rows, seq); got rank-"
                f"{self.bucket_rank} buckets {self.buckets}")
        self._apply = apply_fn
        with observability.span("capture", kind="serve"):
            self.item = GraphItem.capture(apply_fn, params, None,
                                          example_batch=example_batch)
        spec = resource_spec if isinstance(resource_spec, ResourceSpec) \
            else ResourceSpec(resource_spec)
        builder = _resolve_serve_builder(strategy_builder)
        with observability.span("strategy-build", kind="serve"):
            self.strategy = builder.build(self.item, spec)
        logging.info("serve: strategy %s via %s", self.strategy.id,
                     type(builder).__name__)
        self._validate_bucket_memory(spec)
        self._obs = observability if observability.enabled() else None
        self.replicas = [
            ReplicaRuntime(i, program, apply_fn, obs=self._obs)
            for i, program in enumerate(
                self._build_programs(spec, int(replicas)))]
        batch_struct = self.item.batch_struct
        for rep in self.replicas:
            for b in self.buckets:
                try:
                    rep.compile_bucket(b, batch_struct)
                except Exception as e:  # noqa: BLE001 - forensics, re-raise
                    _oom_forensics(
                        e, f"serve aot-compile bucket {b} "
                           f"replica {rep.index}")
                    raise
        observability.record_event(
            "serve-start", f"{len(self.replicas)} replica(s), buckets "
            f"{[(b[0] if len(b) == 1 else b) for b in self.buckets]}, "
            f"strategy {self.strategy.id}")

    # -- bucket memory pre-validation ----------------------------------------

    def _validate_bucket_memory(self, spec):
        """Refuse over-capacity buckets at engine build, BEFORE any param
        placement or XLA compile: a bucket whose predicted peak HBM
        (``CostModel.strategy_memory`` at ``batch_rows=bucket``) exceeds
        capacity x ``AUTODIST_MEM_HEADROOM`` raises a named
        :class:`~autodist_tpu.observability.memory.InfeasibleMemoryError`
        instead of an opaque XLA RESOURCE_EXHAUSTED mid-serve
        (docs/memory.md).  The check itself is fail-open — only a
        POSITIVE refusal propagates."""
        try:
            from autodist_tpu.observability import memory as memory_mod
            from autodist_tpu.tuner.calibration import Calibration
            from autodist_tpu.tuner.cost_model import CostModel, Topology
            cal = Calibration.load()
            model = CostModel(Topology.from_resource_spec(spec), cal)
        except Exception as e:  # noqa: BLE001 - advisory check only
            logging.debug("serve bucket memory check unavailable: %s", e)
            return
        for b in self.buckets:
            rows = b[0]
            label = rows if len(b) == 1 else b
            reason = None
            mem = None
            try:
                mem = model.strategy_memory(self.strategy, self.item,
                                            batch_rows=rows)
                reason = memory_mod.check_feasible(mem)
            except Exception as e:  # noqa: BLE001 - advisory check only
                logging.debug("serve bucket %s memory check failed: %s",
                              b, e)
            if reason:
                observability.record_event(
                    "oom", f"serve bucket {label} refused at engine "
                           f"build: {reason}")
                raise memory_mod.InfeasibleMemoryError(
                    f"serve bucket {label} refused: {reason}; dominant "
                    f"class {mem.dominant_class()} — drop the bucket "
                    f"from AUTODIST_SERVE_BUCKETS or raise "
                    f"AUTODIST_HBM_GB if this accelerator really has "
                    f"more memory")

    # -- mesh carving --------------------------------------------------------

    def _build_programs(self, spec, replicas):
        return build_replica_programs(self.item, self.strategy, spec,
                                      replicas)

    @property
    def program(self):
        """Replica 0's DistributedProgram (report rendering)."""
        return self.replicas[0].program

    @property
    def max_rows(self):
        return max(b[0] for b in self.buckets)

    def least_loaded(self):
        """The replica with the fewest outstanding dispatches (ties go to
        the lowest index — deterministic).  ``self.replicas`` holds only
        LIVE replicas — the outstanding counts live on the replica
        objects themselves, so a removed replica can never be selected
        and never leaks a stale count (docs/serving.md)."""
        return min(self.replicas, key=lambda r: (r.outstanding, r.index))

    def remove_replica(self, index):
        """Remove one live replica mid-flight (forced removal, elastic
        shrink).  The replica's in-flight dispatch (if any) completes
        normally; everything still queued on it drains back as
        ``(batch, group, rows)`` items the caller re-dispatches to the
        survivors (``Server.remove_replica``) — zero requests dropped.
        Raises on an unknown index or the last replica."""
        rep = next((r for r in self.replicas if r.index == index), None)
        if rep is None:
            raise ValueError(
                f"no live replica {index}; live indices "
                f"{[r.index for r in self.replicas]}")
        if len(self.replicas) == 1:
            raise ValueError("cannot remove the last replica")
        self.replicas.remove(rep)
        drained = rep.drain_close()
        observability.record_event(
            "serve-scale", f"replica {index} removed "
            f"({len(drained)} queued item(s) to re-dispatch, "
            f"{len(self.replicas)} left)")
        return drained

    def start(self, on_complete, depth=None):
        for rep in self.replicas:
            rep.start(on_complete, depth=depth)

    def close(self):
        for rep in self.replicas:
            rep.close()
