"""Continuously-batched inference server.

Request lifecycle::

    submit(batch) -> Future          # any leading-dim size that fits a bucket
      -> coalescer (FIFO queue): requests group into the smallest
         admissible bucket under a max-wait deadline (the OLDEST request
         in a group bounds its wait — a lone request is never starved)
      -> least-loaded replica: the group's rows are packed FIFO into a
         zero-padded bucket batch and enqueued on the replica with the
         fewest outstanding dispatches
      -> replica executor: depth-N prefetch window shards the batch onto
         the replica's mesh (transfer overlaps the current execute),
         the bucket's AOT executable runs (params resident, never
         donated), outputs come back to host
      -> de-padding: each request's exact rows are sliced back out, in
         submission order, and resolve its Future.

Telemetry (``serve.*`` metrics, report "Serving" section): per-request
latency histogram (p50/p99), queue depth, padded-row overhead, and
per-replica dispatch/outstanding/utilization gauges.
"""
import itertools
import queue
import threading
import time

from concurrent.futures import Future

import numpy as np
import jax

from autodist_tpu import const, observability
from autodist_tpu.serve.buckets import buckets_from_env, pick_bucket
from autodist_tpu.serve.engine import ServeEngine
from autodist_tpu.utils import logging

_STOP = object()
#: Milliseconds the oldest queued request waits for companions.
MAX_WAIT_MS = 5


class _Request:
    __slots__ = ("seq", "batch", "rows", "seq_len", "future", "t_submit")

    def __init__(self, seq, batch, rows, seq_len=None):
        self.seq = seq
        self.batch = batch
        self.rows = rows
        self.seq_len = seq_len   # dim-1 length under (rows, seq) buckets
        self.future = Future()
        self.t_submit = time.perf_counter()


class Server:
    """Continuously-batched serving front-end over a :class:`ServeEngine`.

    Args:
        apply_fn: ``(params, batch) -> outputs`` forward function; outputs
            must be batch-major (leading dim = batch rows) and row-
            independent (no cross-example coupling — padding rows are
            zeros and are sliced off, they must not perturb real rows).
        params: parameter pytree (placed once per replica, never donated).
        example_batch: example request pytree; dim 0 is the batch
            dimension, trailing dims/dtypes are the compile-time contract
            every request must match.
        buckets: padded batch sizes to AOT-compile (default:
            ``AUTODIST_SERVE_BUCKETS``, else ``(8, 32, 128)``).  Each must
            be a multiple of the per-replica device count.
        max_wait_ms: continuous-batching coalesce deadline (default
            :data:`MAX_WAIT_MS`): how long the oldest queued
            request may wait for companions before its bucket dispatches.
        replicas: independent model replicas to carve the mesh into
            (least-loaded dispatch; data-only strategies).
        strategy_builder / resource_spec: the training stack's policy
            points, unchanged (``AUTODIST_STRATEGY=auto`` routes through
            the tuner's ``serve_latency`` objective).
    """

    def __init__(self, apply_fn, params, example_batch, buckets=None,
                 max_wait_ms=MAX_WAIT_MS, replicas=1, strategy_builder=None,
                 resource_spec=None, prefetch_depth=None):
        bucket_list = buckets_from_env() if buckets is None else buckets
        self._engine = ServeEngine(apply_fn, params, example_batch,
                                   bucket_list,
                                   resource_spec=resource_spec,
                                   strategy_builder=strategy_builder,
                                   replicas=replicas)
        self._buckets = self._engine.buckets
        self._bucket_rank = self._engine.bucket_rank
        self._max_rows = self._engine.max_rows
        self._max_wait_s = max(0.0, float(max_wait_ms)) / 1e3
        self._obs = observability if observability.enabled() else None
        self._seq = itertools.count()
        self._rq = queue.Queue()
        self._closed = False
        self._requests = 0
        self._batches = 0
        self._padded_rows = 0
        self._completed = 0
        self.last_dispatch = None  # {"bucket", "replica", "assignments"}
        self._struct = [(tuple(s.shape), s.dtype) for s in
                        jax.tree_util.tree_leaves(self._engine.item.batch_struct)]
        self._treedef = jax.tree_util.tree_structure(example_batch)
        self._engine.start(self._complete, depth=prefetch_depth)
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="autodist-serve-dispatcher")
        self._dispatcher.start()
        logging.info("serve: server up — %d replica(s), buckets %s, "
                     "max_wait %.1fms", len(self._engine.replicas),
                     [b[0] for b in self._buckets], self._max_wait_s * 1e3)

    # -- public API ----------------------------------------------------------

    @property
    def engine(self):
        return self._engine

    def submit(self, batch):
        """Enqueue one request; returns a ``concurrent.futures.Future``
        resolving to the de-padded outputs for exactly these rows.
        Raises immediately (not on the future) for malformed or oversize
        requests — admission control, not queue poison."""
        if self._closed:
            raise RuntimeError("serve.Server is closed")
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        if treedef != self._treedef:
            raise ValueError(
                f"request structure {treedef} != example_batch structure "
                f"{self._treedef}")
        rank = self._bucket_rank
        rows = seq_len = None
        for leaf, (shape, dtype) in zip(leaves, self._struct):
            got = tuple(np.shape(leaf))
            # Under (rows, seq) buckets the first TWO dims are padded, so
            # only dims beyond the bucket rank are a fixed compile-time
            # contract; ragged prompts vary dim 1 request to request.
            if len(got) != len(shape) or got[rank:] != shape[rank:]:
                raise ValueError(
                    f"request leaf shape {got} incompatible with compiled "
                    f"trailing dims {shape[rank:]} (rank {len(shape)})")
            if rows is None:
                rows = got[0]
                seq_len = got[1] if rank == 2 else None
            elif got[0] != rows or (rank == 2 and got[1] != seq_len):
                raise ValueError(
                    f"request leaves disagree on padded leading dims: "
                    f"{got[:rank]} vs {(rows, seq_len)[:rank]}")
        if not rows:
            raise ValueError("empty request (0 rows)")
        dims = (rows,) if rank == 1 else (rows, seq_len)
        pick_bucket(dims, self._buckets)  # oversize -> loud ValueError
        req = _Request(next(self._seq), batch, rows, seq_len=seq_len)
        self._requests += 1
        self._rq.put(req)
        if self._obs is not None:
            reg = self._obs.registry()
            reg.counter("serve.requests").inc()
            reg.gauge("serve.queue_depth").set(self._rq.qsize())
        return req.future

    def infer(self, batch, timeout=None):
        """Synchronous convenience wrapper: ``submit(batch).result()``."""
        return self.submit(batch).result(timeout=timeout)

    def remove_replica(self, index):
        """Forced mid-flight removal of one replica (a failed host, an
        elastic shrink): the replica's in-flight dispatch completes, its
        still-queued work re-dispatches FIFO to the least-loaded
        survivors, and no future is dropped or failed.  Subsequent
        dispatch only ever consults live replicas — the outstanding
        counts ride on the replica objects, so nothing stale survives
        the removal.  Returns the number of re-dispatched batches."""
        drained = self._engine.remove_replica(index)
        for batch, group, rows in drained:
            rep = self._engine.least_loaded()
            rep.enqueue(batch, group, rows)
        if self._obs is not None:
            self._obs.registry().gauge("serve.replicas").set(
                len(self._engine.replicas))
        logging.info("serve: replica %d removed, %d queued batch(es) "
                     "re-dispatched", index, len(drained))
        return len(drained)

    def stats(self):
        return {
            "requests": self._requests,
            "completed": self._completed,
            "batches": self._batches,
            "padded_rows": self._padded_rows,
            "queue_depth": self._rq.qsize(),
            "buckets": [b[0] for b in self._buckets],
            "replicas": [{
                "index": r.index,
                "dispatches": r.dispatches,
                "outstanding": r.outstanding,
                "utilization": round(r.utilization, 4),
            } for r in self._engine.replicas],
        }

    def close(self):
        """Drain queued requests, stop the dispatcher and replicas."""
        if self._closed:
            return
        self._closed = True
        self._rq.put(_STOP)
        self._dispatcher.join(timeout=60)
        self._engine.close()
        observability.record_event(
            "serve-stop", f"{self._completed}/{self._requests} requests "
            f"completed over {self._batches} batches")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- continuous batching -------------------------------------------------

    def _dispatch_loop(self):
        carry = None
        while True:
            req = carry if carry is not None else self._rq.get()
            carry = None
            if req is _STOP:
                break
            group, rows = [req], req.rows
            # The OLDEST request bounds the group's wait: coalescing may
            # only ever delay a request by max_wait, never starve it.
            deadline = req.t_submit + self._max_wait_s
            while rows < self._max_rows:
                timeout = deadline - time.perf_counter()
                if timeout <= 0:
                    break
                try:
                    nxt = self._rq.get(timeout=timeout)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    carry = _STOP
                    break
                if rows + nxt.rows > self._max_rows:
                    carry = nxt  # doesn't fit: next group starts with it
                    break
                group.append(nxt)
                rows += nxt.rows
            try:
                self._dispatch(group, rows)
            except Exception as e:  # noqa: BLE001 - fail the group's futures
                for r in group:
                    if not r.future.done():
                        r.future.set_exception(e)
            if carry is _STOP:
                break
        # Drain anything still queued after close(): fail fast, don't hang
        # callers on futures that will never resolve.
        while True:
            try:
                item = self._rq.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP and not item.future.done():
                item.future.set_exception(
                    RuntimeError("serve.Server closed before dispatch"))

    def _group_bucket(self, group, rows):
        """The (deterministic) bucket a group dispatches at: total rows,
        and under (rows, seq) buckets the group's max sequence length —
        ragged prompts pad to the smallest admissible grid, not the
        global max seq."""
        if self._bucket_rank == 1:
            return pick_bucket((rows,), self._buckets)
        return pick_bucket((rows, max(r.seq_len for r in group)),
                           self._buckets)

    def _dispatch(self, group, rows):
        bucket = self._group_bucket(group, rows)
        rank = self._bucket_rank
        # Pack FIFO: request i occupies rows [lo_i, lo_i + rows_i); the
        # padding tail is zeros (a row-independent model must be
        # indifferent to it; the tail is sliced off before anyone sees it).
        # Under (rows, seq) buckets each request's dim 1 pads to the
        # bucket seq the same way — zero columns on the right.
        flats = [jax.tree_util.tree_leaves(r.batch) for r in group]
        out = []
        for j, (shape, dtype) in enumerate(self._struct):
            buf = np.zeros(bucket + shape[rank:], dtype)
            lo = 0
            for r, flat in zip(group, flats):
                if rank == 2:
                    buf[lo:lo + r.rows, :r.seq_len] = np.asarray(flat[j])
                else:
                    buf[lo:lo + r.rows] = np.asarray(flat[j])
                lo += r.rows
            out.append(buf)
        batch = jax.tree_util.tree_unflatten(self._treedef, out)
        replica = self._engine.least_loaded()
        assignments, lo = [], 0
        for r in group:
            assignments.append((r.seq, lo, lo + r.rows))
            lo += r.rows
        self.last_dispatch = {
            "bucket": bucket[0] if rank == 1 else bucket,
            "replica": replica.index, "assignments": assignments}
        self._batches += 1
        self._padded_rows += bucket[0] - rows
        replica.enqueue(batch, group, rows)
        if self._obs is not None:
            reg = self._obs.registry()
            reg.counter("serve.batches").inc()
            reg.counter("serve.padded_rows").inc(bucket[0] - rows)
            reg.gauge("serve.queue_depth").set(self._rq.qsize())
            reg.gauge(f"serve.replica{replica.index}.outstanding").set(
                replica.outstanding)

    # -- completion (called on replica executor threads) ---------------------

    def _complete(self, replica, group, host_out, rows):
        now = time.perf_counter()
        bseq = self._group_bucket(group, rows)[1] \
            if self._bucket_rank == 2 else None
        lo = 0
        for r in group:
            hi = lo + r.rows
            sl = slice(lo, hi)

            def depad(a, _sl=sl, _seq=r.seq_len):
                # Under (rows, seq) buckets, outputs that kept the padded
                # seq dim at axis 1 are sliced back to this request's
                # length; other outputs (pooled heads etc.) pass through.
                if bseq is not None and np.ndim(a) >= 2 and \
                        np.shape(a)[1] == bseq:
                    return a[_sl, :_seq]
                return a[_sl]
            r.future.set_result(jax.tree_util.tree_map(depad, host_out))
            lo = hi
        self._completed += len(group)
        if self._obs is not None:
            reg = self._obs.registry()
            hist = reg.histogram("serve.latency_ms")
            hist.observe_many([(now - r.t_submit) * 1e3 for r in group])
            # SLO burn: windowed p99 over the target (AUTODIST_SERVE_SLO_MS).
            # > 1.0 means the p99 is past the SLO — the monitor's pager
            # gauge.  Cold path relative to the dispatch (window <= 256).
            p99 = hist.summary().get("p99")
            if p99 is not None:
                slo = max(1, const.ENV.AUTODIST_SERVE_SLO_MS.val)
                reg.gauge("serve.slo_burn").set(round(p99 / slo, 4))
            i = replica.index
            reg.counter(f"serve.replica{i}.dispatches").inc()
            reg.gauge(f"serve.replica{i}.outstanding").set(
                replica.outstanding)
            reg.gauge(f"serve.replica{i}.utilization").set(
                round(replica.utilization, 4))
            self._observe_measured(hist)

    # -- tuner feedback (docs/tuning.md, docs/serving.md) --------------------

    _CAL_EVERY = 32

    def _observe_measured(self, hist):
        """Feed the measured serve p50 back to the tuner the way training
        step p50s feed it: when this process tuned under the
        ``serve_latency`` objective, the per-request p50 closes the
        predicted-vs-measured loop — ``auto.record_measurement`` puts the
        error on the report's Tuner section, and a ``serve``-term
        calibration observation (context ``serve:bucket<b>``) refines the
        objective's scale for the next run.  Cold path (every
        ``_CAL_EVERY`` completions), fail-open."""
        if self._completed % self._CAL_EVERY:
            return
        try:
            from autodist_tpu.tuner import auto
            result = auto.last_result()
            if result is None or \
                    getattr(result, "objective", None) != "serve_latency":
                return
            p50 = (hist.summary() or {}).get("p50")
            if not p50:
                return
            auto.record_measurement(p50)
            ctx = "serve:bucket" + str(
                (self.last_dispatch or {}).get("bucket"))
            result.calibration.observe_term("serve", result.predicted_ms,
                                            p50, context=ctx)
        except Exception as e:  # noqa: BLE001 - telemetry only
            logging.debug("serve calibration feed skipped: %s", e)
