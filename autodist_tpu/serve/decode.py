"""Autoregressive decode engine: KV-cache continuous batching.

PR 6's serving runtime is a one-shot batch scorer — this module is the
decode half (ROADMAP item 3): token-level continuous batching over a
preallocated, sharded KV cache, where requests JOIN and LEAVE the
in-flight batch at every decode step.

Architecture (docs/serving.md "Autoregressive decode"):

* **Slots, not batches.**  Each replica holds one *lane* per
  ``(slots, cache_len)`` bucket: an AOT-compiled
  ``decode_step(params, cache, tokens, pos)`` executable (same
  never-recompile contract as ``AUTODIST_SERVE_BUCKETS``), a
  device-resident KV cache with the ``slots`` dim sharded over the
  replica's data axis, and a host-side slot table.  A request occupies
  one slot from admission to completion; freed slots refill from the
  FIFO queue at the very next step with ZERO recompiles.
* **Prefill through the decode path.**  Prompts feed token-by-token
  through the same executable (logits ignored until the last prompt
  token), so one step can mix prefilling and decoding slots — the
  token-granularity join/leave that makes continuous batching pay.
* **The cache is a pure optimization.**  Stale rows from a previous
  occupant are never exposed: attention masks ``j <= pos`` and masked
  softmax columns are exactly 0.0 (layers.mha_decode), so decode output
  is bitwise-equal to a full-prefix forward recompute — tier-1 pinned.
* **Zero-drop scaling.**  All request state (prompt + generated tokens)
  is host-side; :meth:`DecodeEngine.scale_to` drains every in-flight
  request, re-carves the mesh into the new replica count, and re-queues
  the drained requests AT THE FRONT in submission order.  Greedy
  continuation re-prefills prompt+generated bitwise-identically, so a
  scale event drops zero requests and changes zero tokens.

The SLO-driven autoscaler that calls ``scale_to`` lives in
``serve/autoscale.py``.
"""
import itertools
import threading
import time

from collections import deque
from concurrent.futures import Future

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec

from autodist_tpu import const, observability
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.serve.buckets import normalize_buckets
from autodist_tpu.serve.engine import (ReplicaRuntime, _oom_forensics,
                                       _resolve_serve_builder,
                                       build_replica_programs)
from autodist_tpu.utils import logging


def decode_buckets_from_env():
    """Default decode bucket list: one ``(slots, cache_len)`` bucket from
    ``AUTODIST_DECODE_SLOTS`` x ``AUTODIST_DECODE_CACHE_LEN``."""
    return ((max(1, const.ENV.AUTODIST_DECODE_SLOTS.val),
             max(1, const.ENV.AUTODIST_DECODE_CACHE_LEN.val)),)


class DecodeRequest:
    """One in-flight generation.  ALL state is host-side (prompt +
    tokens generated so far), so a scale event can evict the request
    from its slot and re-dispatch it with zero loss: the continuation
    re-prefills ``prompt + generated`` through the decode executable,
    which is bitwise-identical under greedy decoding."""

    __slots__ = ("seq", "prompt", "max_new_tokens", "eos", "generated",
                 "future", "t_submit", "redispatches")

    def __init__(self, seq, prompt, max_new_tokens, eos=None):
        self.seq = seq
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos = None if eos is None else int(eos)
        self.generated = []
        self.future = Future()
        self.t_submit = time.perf_counter()
        self.redispatches = 0

    @property
    def tokens(self):
        """The effective input stream: prompt, then everything generated
        so far (a re-dispatched continuation prefills through both)."""
        return self.prompt + self.generated

    @property
    def need(self):
        """Cache rows this request can ever touch — admission fits it
        only into lanes with ``cache_len >= need``."""
        return len(self.prompt) + self.max_new_tokens


class _Slot:
    __slots__ = ("req", "pos")

    def __init__(self, req):
        self.req = req
        self.pos = 0   # next cache position to write (tokens fed so far)


class _Lane:
    """One (slots, cache_len) bucket on one replica: the compiled decode
    executable, its device-resident KV cache, and the slot table."""

    def __init__(self, replica, slots, cache_len, fn, cache, row_sharding):
        self.replica = replica
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.fn = fn
        self.cache = cache
        self._row_sh = row_sharding
        self.table = [None] * self.slots
        self.steps = 0

    @property
    def active(self):
        return sum(1 for s in self.table if s is not None)

    def free_slot(self):
        for i, s in enumerate(self.table):
            if s is None:
                return i
        return None

    def place(self, req):
        i = self.free_slot()
        self.table[i] = _Slot(req)
        return i

    def evict_all(self):
        """Pull every in-flight request out (scale drain).  Slot position
        state is discarded — the continuation re-prefills from the
        request's host-side tokens."""
        reqs = [s.req for s in self.table if s is not None]
        self.table = [None] * self.slots
        return reqs

    def step(self):
        """One decode step over every active slot.  Returns
        ``(completed_requests, tokens_generated)``.  Inactive slots feed
        token 0 at position 0 — harmless, because a future occupant's
        prefill overwrites position 0 before the mask ever exposes it."""
        tok = np.zeros((self.slots,), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        emit = []
        for i, s in enumerate(self.table):
            if s is None:
                continue
            toks = s.req.tokens
            tok[i] = toks[s.pos]
            pos[i] = s.pos
            if s.pos == len(toks) - 1:
                emit.append(i)   # last known token: logits sample a new one
        rep = self.replica
        logits, self.cache = self.fn(
            rep.params, self.cache,
            jax.device_put(tok, self._row_sh),
            jax.device_put(pos, self._row_sh))
        self.steps += 1
        host = np.asarray(jax.device_get(logits)) if emit else None
        completed = []
        for i, s in enumerate(self.table):
            if s is not None:
                s.pos += 1
        for i in emit:
            s = self.table[i]
            req = s.req
            nxt = int(host[i].argmax())   # greedy: deterministic continuation
            req.generated.append(nxt)
            if len(req.generated) >= req.max_new_tokens or \
                    (req.eos is not None and nxt == req.eos):
                completed.append(req)
                self.table[i] = None      # slot freed: refilled next step
        return completed, len(emit)


class DecodeReplica(ReplicaRuntime):
    """A :class:`ReplicaRuntime` (mesh slice, resident never-donated
    params, pad-and-mask plan) whose executables are decode steps over a
    donated-on-TPU KV cache instead of one-shot forwards.  The queue/
    prefetch machinery of the base class is unused — lanes step
    synchronously on the engine's replica thread."""

    def __init__(self, index, program, decode_fn, obs=None):
        super().__init__(index, program, decode_fn, obs=obs)
        self.lanes = []

    def compile_decode(self, bucket, init_cache_fn, decode_fn):
        """AOT-compile ``decode_step`` at one (slots, cache_len) bucket
        and preallocate its sharded KV cache.  The ``slots`` dim of the
        cache (and of tokens/pos) shards over the replica's data axis —
        the cache is just one more sharded operand on the same mesh the
        strategy machinery already carved (GSPMD's observation)."""
        slots, cache_len = int(bucket[0]), int(bucket[1])
        n = self.program.data_axis_size
        if slots % n:
            raise ValueError(
                f"decode bucket slots={slots} not divisible by this "
                f"replica's data-axis size {n}; pick AUTODIST_DECODE_SLOTS "
                f"as a multiple of the per-replica device count")
        cache_struct = jax.eval_shape(
            lambda: init_cache_fn(slots, cache_len))
        tp_struct = jax.ShapeDtypeStruct((slots,), np.int32)
        mesh = self.program.mesh
        data = const.MESH_AXIS_DATA if const.MESH_AXIS_DATA in \
            mesh.axis_names else None
        row_sh = NamedSharding(mesh, PartitionSpec(data))
        cache_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(
                mesh, PartitionSpec(data, *([None] * (len(s.shape) - 1)))),
            cache_struct)
        param_sh = self.program.param_shardings()

        def fn(params, cache, tokens, pos):
            return decode_fn(self._unpad_params(params), cache, tokens, pos)

        # Donate the cache where the backend honors it (TPU/GPU): the
        # functional update then writes in place, so the preallocated
        # cache never doubles.  Params are NEVER donated.
        donate = (1,) if mesh.devices.flat[0].platform != "cpu" else ()
        obs = self._obs
        t0 = time.perf_counter()
        with (obs.span("serve-aot-compile", bucket=f"{slots}x{cache_len}",
                       replica=self.index, kind="decode")
              if obs is not None else observability.tracing.NULL_SPAN):
            compiled = jax.jit(
                fn, in_shardings=(param_sh, cache_sh, row_sh, row_sh),
                donate_argnums=donate) \
                .lower(self.params, cache_struct, tp_struct, tp_struct) \
                .compile()
        dt_ms = (time.perf_counter() - t0) * 1e3
        logging.info("decode: replica %d compiled bucket %dx%d (%.0fms)",
                     self.index, slots, cache_len, dt_ms)
        if obs is not None:
            obs.registry().gauge("serve.aot_compile.ms").set(round(dt_ms, 3))
            obs.record_event(
                "serve-compile", f"decode replica {self.index} bucket "
                f"{slots}x{cache_len} ({dt_ms:.0f}ms)")
        cache = jax.device_put(init_cache_fn(slots, cache_len), cache_sh)
        lane = _Lane(self, slots, cache_len, compiled, cache, row_sh)
        self.lanes.append(lane)
        return lane

    def best_lane_for(self, req):
        """The smallest-cache lane with a free slot that fits ``req``
        (deterministic; ``None`` when nothing here fits right now)."""
        fits = [ln for ln in self.lanes
                if ln.cache_len >= req.need and ln.free_slot() is not None]
        return min(fits, key=lambda ln: (ln.cache_len, ln.slots)) \
            if fits else None

    @property
    def active(self):
        return sum(ln.active for ln in self.lanes)

    def release(self):
        """Drop device references (params + lane caches) after a scale
        event replaced this replica."""
        self.lanes = []
        self.params = None


class DecodeEngine:
    """capture -> strategy -> per-replica decode lanes, plus the
    continuous-batching step loops (one thread per replica) and the
    zero-drop :meth:`scale_to`.  The :class:`DecodeServer` owns request
    admission policy and telemetry in front of this."""

    def __init__(self, apply_fn, decode_fn, init_cache_fn, params,
                 example_batch, buckets=None, resource_spec=None,
                 strategy_builder=None, replicas=1):
        bucket_list = decode_buckets_from_env() if buckets is None \
            else buckets
        self.buckets = normalize_buckets(bucket_list)
        if any(len(b) != 2 for b in self.buckets):
            raise ValueError(
                f"decode buckets are (slots, cache_len) pairs; got "
                f"{self.buckets}")
        self._decode = decode_fn
        self._init_cache = init_cache_fn
        # The strategy machinery prices/shards the FORWARD program —
        # decode reuses its param shardings and mesh carving; the KV
        # cache rides the data axis like any batch operand.
        with observability.span("capture", kind="decode"):
            self.item = GraphItem.capture(apply_fn, params, None,
                                          example_batch=example_batch)
        spec = resource_spec if isinstance(resource_spec, ResourceSpec) \
            else ResourceSpec(resource_spec)
        self._spec = spec
        builder = _resolve_serve_builder(strategy_builder)
        with observability.span("strategy-build", kind="decode"):
            self.strategy = builder.build(self.item, spec)
        logging.info("decode: strategy %s via %s", self.strategy.id,
                     type(builder).__name__)
        self._obs = observability if observability.enabled() else None
        self._validate_bucket_memory(spec)
        self._queue = deque()
        self._cv = threading.Condition()
        self._pause = False
        self._closed = False
        self._threads = []
        self._on_complete = None
        self.scale_events = 0
        self.replicas = []
        self._build_fleet(int(replicas))
        observability.record_event(
            "serve-start", f"decode engine: {len(self.replicas)} "
            f"replica(s), buckets "
            f"{['x'.join(map(str, b)) for b in self.buckets]}, "
            f"strategy {self.strategy.id}")

    # -- bucket memory pre-validation ----------------------------------------

    def _validate_bucket_memory(self, spec):
        """Refuse over-capacity decode buckets before any compile: the
        KV cache is priced as its own ledger class
        (``kv_cache_bytes``, docs/memory.md) on top of the forward's
        footprint at ``batch_rows=slots``.  Fail-open — only a POSITIVE
        refusal propagates."""
        try:
            from autodist_tpu.observability import memory as memory_mod
            from autodist_tpu.tuner.calibration import Calibration
            from autodist_tpu.tuner.cost_model import CostModel, Topology
            cal = Calibration.load()
            model = CostModel(Topology.from_resource_spec(spec), cal)
        except Exception as e:  # noqa: BLE001 - advisory check only
            logging.debug("decode bucket memory check unavailable: %s", e)
            return
        for b in self.buckets:
            slots, cache_len = b
            reason = None
            mem = None
            try:
                kv = self.cache_bytes(slots, cache_len)
                mem = model.strategy_memory(self.strategy, self.item,
                                            batch_rows=slots,
                                            kv_cache_bytes=kv)
                reason = memory_mod.check_feasible(mem)
            except Exception as e:  # noqa: BLE001 - advisory check only
                logging.debug("decode bucket %s memory check failed: %s",
                              b, e)
            if reason:
                observability.record_event(
                    "oom", f"decode bucket {slots}x{cache_len} refused "
                           f"at engine build: {reason}")
                raise memory_mod.InfeasibleMemoryError(
                    f"decode bucket {slots}x{cache_len} refused: "
                    f"{reason}; dominant class {mem.dominant_class()} — "
                    f"shrink AUTODIST_DECODE_SLOTS / "
                    f"AUTODIST_DECODE_CACHE_LEN or raise AUTODIST_HBM_GB")

    def cache_bytes(self, slots, cache_len):
        """Total KV-cache bytes of one (slots, cache_len) lane."""
        struct = jax.eval_shape(lambda: self._init_cache(slots, cache_len))
        return float(sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree_util.tree_leaves(struct)))

    # -- fleet build / scale -------------------------------------------------

    def _build_fleet(self, replicas):
        programs = build_replica_programs(self.item, self.strategy,
                                          self._spec, replicas)
        self.replicas = []
        for i, program in enumerate(programs):
            rep = DecodeReplica(i, program, self._decode, obs=self._obs)
            for b in self.buckets:
                try:
                    rep.compile_decode(b, self._init_cache, self._decode)
                except Exception as e:  # noqa: BLE001 - forensics, re-raise
                    _oom_forensics(
                        e, f"decode aot-compile bucket {b} replica {i}")
                    raise
            self.replicas.append(rep)

    @property
    def max_cache_len(self):
        return max(b[1] for b in self.buckets)

    def start(self, on_complete):
        self._on_complete = on_complete
        self._start_threads()

    def _start_threads(self):
        self._pause = False
        self._threads = []
        for rep in self.replicas:
            t = threading.Thread(
                target=self._run_replica, args=(rep,), daemon=True,
                name=f"autodist-decode-replica-{rep.index}")
            self._threads.append(t)
            t.start()

    def _stop_threads(self):
        with self._cv:
            self._pause = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=60)
        self._threads = []

    def scale_to(self, replicas):
        """Re-carve the fleet to ``replicas`` with ZERO dropped requests:
        step loops stop, every in-flight request is evicted (its host-
        side prompt+generated state intact), the mesh re-carves, and the
        evicted requests rejoin at the FRONT of the queue in submission
        order — greedy continuation is bitwise-identical, so tokens
        already streamed stay valid."""
        replicas = int(replicas)
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if replicas == len(self.replicas):
            return 0
        t0 = time.perf_counter()
        old = len(self.replicas)
        self._stop_threads()
        inflight = []
        for rep in self.replicas:
            for lane in rep.lanes:
                inflight.extend(lane.evict_all())
        inflight.sort(key=lambda r: r.seq)
        for r in inflight:
            r.redispatches += 1
        with self._cv:
            self._queue.extendleft(reversed(inflight))
        for rep in self.replicas:
            rep.release()
        self._build_fleet(replicas)
        self._start_threads()
        self.scale_events += 1
        dt_ms = (time.perf_counter() - t0) * 1e3
        observability.record_event(
            "serve-scale", f"decode fleet {old} -> {replicas} replica(s): "
            f"{len(inflight)} in-flight re-dispatched, 0 dropped "
            f"({dt_ms:.0f}ms)")
        if self._obs is not None:
            reg = self._obs.registry()
            reg.gauge("decode.replicas").set(replicas)
            reg.counter("decode.scale_events").inc()
        logging.info("decode: scaled %d -> %d replicas (%d in-flight "
                     "re-dispatched, %.0fms)", old, replicas,
                     len(inflight), dt_ms)
        return len(inflight)

    # -- admission + step loop ----------------------------------------------

    def enqueue(self, req):
        with self._cv:
            self._queue.append(req)
            self._cv.notify_all()

    def queue_depth(self):
        return len(self._queue)

    @property
    def in_flight(self):
        return sum(rep.active for rep in self.replicas)

    def _admit_locked(self, rep):
        """Fill ``rep``'s free slots from the queue head — STRICT FIFO:
        when the head request only fits a lane that is currently full
        (here or on another replica), nothing behind it jumps the line.
        Called with the condition lock held."""
        admitted = 0
        while self._queue:
            lane = rep.best_lane_for(self._queue[0])
            if lane is None:
                break
            lane.place(self._queue.popleft())
            admitted += 1
        return admitted

    def _run_replica(self, rep):
        while True:
            with self._cv:
                if self._pause:
                    break
                self._admit_locked(rep)
                if rep.active == 0:
                    self._cv.wait(timeout=0.02)
                    if self._pause:
                        break
                    self._admit_locked(rep)
                    if rep.active == 0:
                        continue
            for lane in rep.lanes:
                if lane.active == 0:
                    continue
                try:
                    completed, generated = lane.step()
                except Exception as e:  # noqa: BLE001 - fail lane occupants
                    _oom_forensics(e, f"decode step replica {rep.index}")
                    for req in lane.evict_all():
                        if not req.future.done():
                            req.future.set_exception(e)
                    continue
                if self._obs is not None:
                    reg = self._obs.registry()
                    reg.counter("decode.steps").inc()
                    if generated:
                        reg.counter("decode.tokens").inc(generated)
                    reg.gauge("decode.active_slots").set(self.in_flight)
                for req in completed:
                    if self._on_complete is not None:
                        self._on_complete(req)

    def close(self):
        self._stop_threads()
        self._closed = True
        # Fail whatever never ran — a deliberate close, not a drop.
        leftovers = list(self._queue)
        self._queue.clear()
        for rep in self.replicas:
            for lane in rep.lanes:
                leftovers.extend(lane.evict_all())
        for req in leftovers:
            if not req.future.done():
                req.future.set_exception(
                    RuntimeError("decode engine closed before completion"))


class DecodeServer:
    """Request front-end over a :class:`DecodeEngine`:
    ``submit(prompt) -> Future`` resolving to the generated token ids,
    per-request telemetry (``decode.*`` metrics + the ``serve.slo_burn``
    gauge the autoscaler watches), and the zero-drop ``scale_to``.

    Args:
        apply_fn: forward ``(params, batch) -> logits`` — captured for
            the strategy machinery only (shardings, pricing).
        decode_fn: ``(params, cache, tokens, pos) -> (logits, cache)``
            single-token step (e.g. ``models.lm.make_decode_fn(cfg)``).
        init_cache_fn: ``(slots, cache_len) -> cache pytree`` (e.g.
            ``lambda s, l: models.lm.init_decode_cache(cfg, s, l)``).
        params: parameter pytree (placed per replica, never donated).
        example_batch: forward example for capture (dim 0 = batch).
        buckets: (slots, cache_len) pairs to AOT-compile (default: one
            bucket from ``AUTODIST_DECODE_SLOTS`` x
            ``AUTODIST_DECODE_CACHE_LEN``).
        replicas / strategy_builder / resource_spec: as serve.Server.
    """

    def __init__(self, apply_fn, decode_fn, init_cache_fn, params,
                 example_batch, buckets=None, replicas=1,
                 strategy_builder=None, resource_spec=None):
        self._engine = DecodeEngine(
            apply_fn, decode_fn, init_cache_fn, params, example_batch,
            buckets=buckets, resource_spec=resource_spec,
            strategy_builder=strategy_builder, replicas=replicas)
        self._obs = observability if observability.enabled() else None
        self._seq = itertools.count()
        self._closed = False
        self._requests = 0
        self._completed = 0
        self._tokens = 0
        self._t0 = time.perf_counter()
        if self._obs is not None:
            self._obs.registry().gauge("decode.replicas").set(
                len(self._engine.replicas))
        self._engine.start(self._finished)
        logging.info(
            "decode: server up — %d replica(s), buckets %s",
            len(self._engine.replicas),
            ["x".join(map(str, b)) for b in self._engine.buckets])

    @property
    def engine(self):
        return self._engine

    # -- public API ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens=16, eos=None):
        """Enqueue one generation; returns a Future resolving to the
        np.int32 array of generated token ids.  Oversize requests
        (prompt + budget beyond every lane's cache) fail loudly here —
        admission control, not queue poison."""
        if self._closed:
            raise RuntimeError("serve.DecodeServer is closed")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        need = len(prompt) + int(max_new_tokens)
        if need > self._engine.max_cache_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) = {need} exceeds the largest decode "
                f"cache_len {self._engine.max_cache_len}; raise "
                f"AUTODIST_DECODE_CACHE_LEN or shorten the request")
        req = DecodeRequest(next(self._seq), prompt, max_new_tokens,
                            eos=eos)
        self._requests += 1
        self._engine.enqueue(req)
        if self._obs is not None:
            reg = self._obs.registry()
            reg.counter("decode.requests").inc()
            reg.gauge("decode.queue_depth").set(
                self._engine.queue_depth())
        return req.future

    def generate(self, prompt, max_new_tokens=16, eos=None, timeout=None):
        """Synchronous convenience wrapper."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos=eos).result(timeout=timeout)

    def scale_to(self, replicas):
        """Grow/shrink the replica fleet; zero requests dropped."""
        return self._engine.scale_to(replicas)

    def stats(self):
        return {
            "requests": self._requests,
            "completed": self._completed,
            "tokens": self._tokens,
            "queue_depth": self._engine.queue_depth(),
            "in_flight": self._engine.in_flight,
            "replicas": len(self._engine.replicas),
            "scale_events": self._engine.scale_events,
            "buckets": [tuple(b) for b in self._engine.buckets],
        }

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._engine.close()
        observability.record_event(
            "serve-stop", f"decode: {self._completed}/{self._requests} "
            f"requests, {self._tokens} tokens")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- completion (engine replica threads) ---------------------------------

    def _finished(self, req):
        if req.future.done():   # exactly-once: a drain race never double-fires
            return
        now = time.perf_counter()
        self._completed += 1
        self._tokens += len(req.generated)
        req.future.set_result(np.asarray(req.generated, np.int32))
        if self._obs is not None:
            reg = self._obs.registry()
            hist = reg.histogram("decode.latency_ms")
            hist.observe((now - req.t_submit) * 1e3)
            elapsed = max(1e-9, now - self._t0)
            reg.gauge("decode.tokens_per_sec").set(
                round(self._tokens / elapsed, 2))
            reg.gauge("decode.queue_depth").set(
                self._engine.queue_depth())
            # The SAME pager gauge the one-shot server maintains: the
            # autoscaler watches serve.slo_burn regardless of which
            # serving front-end is live (docs/serving.md).
            p99 = (hist.summary() or {}).get("p99")
            if p99 is not None:
                slo = max(1, const.ENV.AUTODIST_SERVE_SLO_MS.val)
                reg.gauge("serve.slo_burn").set(round(p99 / slo, 4))
