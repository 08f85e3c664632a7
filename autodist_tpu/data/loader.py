"""Data pipeline: zero-copy sharded native loader + depth-N device prefetch.

Role parity: the reference feeds graphs through feed_dict splitting and TF's
C++ input stack (queues/iterators, ``op_info.py:119-149``); here the
framework owns the native layer itself:

* :class:`NativeDataLoader` — ctypes binding to ``native/prefetcher.cpp``:
  C++ threads assemble shuffled batches from a memory-mapped record file,
  GIL-free, into a small pool of reusable caller-owned staging buffers
  (:class:`BufferPool`) — no per-batch allocation on the steady path — with
  a multi-slot async assembly ring (``loader_next_async`` per pool buffer)
  overlapping assembly with the consumer's transfer work.  Per-host
  sharding (``per_host=True`` / ``shard_index``+``shard_count``) stripes
  the record file so each process reads only its own range, and
  ``block_shuffle=True`` shuffles contiguous batch-sized blocks instead of
  records, enabling true zero-copy hand-out: batches are read-only views
  straight into the mmap.  Compiled on first use with g++ into the working
  dir (no pip deps); :class:`_PyLoaderImpl` is the pure-Python fallback
  with identical semantics.
* :class:`DevicePrefetcher` — wraps any batch iterator and keeps ``depth``
  transfers in flight onto the mesh with explicit completion handles,
  settling each batch just-in-time before hand-out so H2D overlaps step
  compute, and returning staging buffers to the loader's pool once their
  transfer retired.  One code path replaces the previous three divergent
  modes (threaded / pipelined single-core / passthrough).

The depths are constants (docs/data.md): :data:`PREFETCH_DEPTH`,
:data:`LOADER_RING`; a staging pool holds what those two keep busy plus two.
"""
import ctypes
import hashlib
import os
import queue
import subprocess
import threading
import time

from collections import deque

import jax
import numpy as np

from autodist_tpu import const, observability
from autodist_tpu.utils import logging

#: Transfers a :class:`DevicePrefetcher` keeps in flight (the Runner's
#: loader wiring and the memory ledger's input term read it from here).
PREFETCH_DEPTH = 2
#: Batches the native async assembly ring fills ahead.
LOADER_RING = 2

_SRC = os.path.join(os.path.dirname(__file__), "native", "prefetcher.cpp")
_lib = None
_lib_err = None


def _native_lib_path():
    """Where this checkout's build of the native loader lives: the file
    name carries a hash of the source's bytes, so two checkouts sharing the
    working dir never load each other's build."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(const.DEFAULT_WORKING_DIR,
                        f"libprefetcher-{digest}.so")


def _build_native():
    """Compile the native loader (cached in the working dir)."""
    global _lib, _lib_err
    if _lib is not None or _lib_err is not None:
        return _lib
    const.ensure_working_dirs()
    try:
        so_path = _native_lib_path()
        if not os.path.exists(so_path):
            # Build beside the target and rename: a concurrent process
            # never loads a half-written library.
            tmp_path = f"{so_path}.{os.getpid()}.tmp"
            cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
                   _SRC, "-o", tmp_path]
            subprocess.run(cmd, check=True, capture_output=True, text=True)
            os.replace(tmp_path, so_path)
            logging.info("built native data loader: %s", so_path)
        lib = ctypes.CDLL(so_path)
        lib.loader_create.restype = ctypes.c_void_p
        lib.loader_create.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                      ctypes.c_int64, ctypes.c_int64,
                                      ctypes.c_uint64, ctypes.c_int]
        lib.loader_create_ex.restype = ctypes.c_void_p
        lib.loader_create_ex.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int64,
                                         ctypes.c_uint64, ctypes.c_int,
                                         ctypes.c_int64, ctypes.c_int64,
                                         ctypes.c_int]
        lib.loader_next.restype = ctypes.c_int
        lib.loader_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.loader_next_view.restype = ctypes.c_int
        lib.loader_next_view.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_void_p)]
        lib.loader_next_async.restype = ctypes.c_int
        lib.loader_next_async.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.loader_next_wait.restype = ctypes.c_int
        lib.loader_next_wait.argtypes = [ctypes.c_void_p]
        lib.loader_async_pending.restype = ctypes.c_int64
        lib.loader_async_pending.argtypes = [ctypes.c_void_p]
        lib.loader_num_samples.restype = ctypes.c_int64
        lib.loader_num_samples.argtypes = [ctypes.c_void_p]
        lib.loader_stats.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_int64)]
        lib.loader_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
    except Exception as e:  # noqa: BLE001 - toolchain may be absent
        _lib_err = e
        logging.warning("native loader unavailable (%s); using Python "
                        "fallback", e)
    return _lib


def write_record_file(path, array):
    """Write (N, ...) array as a flat fixed-size-record file.

    Streams via ``ndarray.tofile`` — O(1) extra memory; ``tobytes`` would
    materialize a full second copy of the dataset on the host.
    """
    arr = np.ascontiguousarray(array)
    with open(path, "wb") as f:
        arr.tofile(f)
    return arr[0].nbytes, arr.shape[1:], arr.dtype


class BufferPool:
    """Small pool of reusable staging buffers (one batch each).

    ``acquire`` hands out a free buffer, allocating only while the pool is
    below ``size``; once warm, the steady state allocates nothing as long
    as the consumer keeps returning buffers with ``release``.  A consumer
    that holds on to every buffer degrades gracefully: acquire falls back
    to a fresh allocation (counted in ``fallback_allocs``) instead of
    blocking or failing.  ``release`` ignores foreign arrays (wrong
    shape/dtype or views), so callers can blanket-release every leaf of a
    heterogeneous batch pytree.
    """

    def __init__(self, shape, dtype, size):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.size = max(1, int(size))
        self.fallback_allocs = 0
        self._allocated = 0
        self._free = []
        self._lock = threading.Lock()

    def acquire(self):
        with self._lock:
            if self._free:
                return self._free.pop()
            if self._allocated >= self.size:
                self.fallback_allocs += 1
            self._allocated += 1
        return np.empty(self.shape, self.dtype)

    def release(self, buf):
        """Return a buffer to the pool; no-op for arrays it cannot reuse."""
        if (not isinstance(buf, np.ndarray) or buf.shape != self.shape
                or buf.dtype != self.dtype or not buf.flags.owndata):
            return False
        with self._lock:
            if len(self._free) < self.size:
                self._free.append(buf)
                return True
        return False

    @property
    def outstanding(self):
        with self._lock:
            return self._allocated - len(self._free)


def _resolve_shard(shard_index, shard_count, per_host):
    """(index, count) for per-host striping; (0, 1) when unsharded."""
    if per_host and shard_index is None and shard_count is None:
        try:
            shard_index = jax.process_index()
            shard_count = jax.process_count()
        except Exception:  # noqa: BLE001 - pre-distributed-init
            shard_index, shard_count = 0, 1
    shard_index = 0 if shard_index is None else int(shard_index)
    shard_count = 1 if shard_count is None else int(shard_count)
    if not 0 <= shard_index < shard_count:
        raise ValueError(f"shard_index {shard_index} outside "
                         f"[0, {shard_count})")
    return shard_index, shard_count


class NativeDataLoader:
    """Shuffling batch iterator over a record file (C++ threads).

    Yields (batch_size,) + record_shape arrays of the record dtype, forever
    (epochs reshuffle with a per-epoch seed).

    Batches come from a :class:`BufferPool` of reusable staging buffers:
    the consumer should hand each batch back via :meth:`recycle` once it is
    done (the :class:`DevicePrefetcher` does this automatically when its
    transfer retires) — unreturned buffers degrade to fresh allocations,
    never to corruption.  With ``block_shuffle=True`` batches are read-only
    zero-copy VIEWS into the record-file mmap (shuffle granularity: whole
    batch-sized blocks); ``recycle`` is a no-op for views.

    ``per_host=True`` (or explicit ``shard_index``/``shard_count``) stripes
    the record file across processes: this loader sees only its contiguous
    ``num_samples``-record range, asserted via :meth:`stats`.
    """

    def __init__(self, path, record_shape, dtype, batch_size, seed=0,
                 capacity=8, num_threads=None, pipeline=None,
                 shard_index=None, shard_count=None, per_host=False,
                 block_shuffle=False, pool_size=None, ring_depth=None):
        """``pipeline=True`` keeps an async assembly ring of up to
        ``ring_depth`` batches (default :data:`LOADER_RING`) filling
        ahead in a native (GIL-free) thread: ``__next__`` hands out the
        oldest completed assembly and tops the ring back up, so the memcpy
        overlaps whatever the consumer does next (issuing the H2D
        transfer, dispatching the step).  Default: on for the zero-thread
        mode (where it is the only overlap available), off when a worker
        pool already assembles ahead.  ``block_shuffle`` implies neither:
        views need no assembly at all.
        """
        if num_threads is None:
            # Worker threads only help when there is a core for them: on a
            # single-core host they timeshare against the consumer and the
            # accelerator runtime, slowing the whole pipeline — use the
            # synchronous zero-thread mode there.  (The async assembly ring
            # is a different regime: it fills only while the consumer waits
            # on a transfer.)
            num_threads = 0 if (os.cpu_count() or 1) <= 1 else 2
        if block_shuffle:
            num_threads = 0  # views are synchronous: nothing to assemble
        if pipeline is None:
            pipeline = num_threads == 0 and not block_shuffle
        self.record_shape = tuple(record_shape)
        self.dtype = np.dtype(dtype)
        self.batch_size = batch_size
        self.block_shuffle = block_shuffle
        self.shard_index, self.shard_count = _resolve_shard(
            shard_index, shard_count, per_host)
        sample_bytes = int(np.prod(self.record_shape, dtype=np.int64) *
                           self.dtype.itemsize) if self.record_shape else \
            self.dtype.itemsize
        self._impl = None
        lib = _build_native()
        if lib is not None:
            h = lib.loader_create_ex(
                str(path).encode(), sample_bytes, batch_size, capacity,
                seed, num_threads, self.shard_index, self.shard_count,
                1 if block_shuffle else 0)
            if h:
                self._impl = ("native", lib, ctypes.c_void_p(h))
        if self._impl is None:
            self._impl = ("python",
                          _PyLoaderImpl(path, sample_bytes, batch_size,
                                        seed, capacity,
                                        shard_index=self.shard_index,
                                        shard_count=self.shard_count,
                                        block_shuffle=block_shuffle), None)
        self._sample_bytes = sample_bytes
        # Async assembly ring (native zero-thread mode only; see ctor doc).
        if ring_depth is None:
            ring_depth = LOADER_RING
        self._ring_depth = (min(ring_depth, max(1, capacity))
                            if (pipeline and self._impl[0] == "native"
                                and num_threads == 0 and not block_shuffle)
                            else 0)
        self._ring = deque()  # buffers with a queued/running async assembly
        if pool_size is None:
            pool_size = self._ring_depth + PREFETCH_DEPTH + 2
        self._pool = BufferPool((batch_size,) + self.record_shape,
                                self.dtype, pool_size)

    @property
    def backend(self):
        return self._impl[0]

    @property
    def num_samples(self):
        """Records in THIS shard's stripe (== the whole file unsharded)."""
        kind, lib, h = self._impl
        if kind == "native":
            return int(lib.loader_num_samples(h))
        return lib.num_samples

    @property
    def pool(self):
        return self._pool

    def recycle(self, buf):
        """Return a previously yielded batch buffer to the staging pool.

        Safe to call with anything: foreign arrays (labels, views, device
        arrays) are ignored.  Call only once the batch's bytes are no
        longer needed — i.e. after the device transfer consuming it has
        retired (the DevicePrefetcher settles before recycling).
        """
        self._pool.release(buf)

    def stats(self):
        """Read accounting: {records_read, min_index, max_index} with
        min/max as GLOBAL record-file indices (None before the first
        read) — lets a multi-process test assert this process never
        touched records outside its stripe."""
        kind, lib, h = self._impl
        if kind == "native":
            out = (ctypes.c_int64 * 3)()
            lib.loader_stats(h, out)
            read, lo, hi = int(out[0]), int(out[1]), int(out[2])
        elif kind == "python":
            read, lo, hi = lib.stats()
        else:
            read, lo, hi = 0, -1, -1
        return {"records_read": read,
                "min_index": None if lo < 0 else lo,
                "max_index": None if hi < 0 else hi,
                "pool_fallback_allocs": self._pool.fallback_allocs}

    def __iter__(self):
        return self

    def _next_view(self, lib, h):
        """Zero-copy hand-out: a read-only array over the mmap'd block."""
        ptr = ctypes.c_void_p()
        rc = lib.loader_next_view(h, ctypes.byref(ptr))
        if rc != 0:
            raise StopIteration
        nbytes = self.batch_size * self._sample_bytes
        raw = (ctypes.c_uint8 * nbytes).from_address(ptr.value)
        out = np.frombuffer(raw, dtype=self.dtype).reshape(
            (self.batch_size,) + self.record_shape)
        out.flags.writeable = False
        return out

    def __next__(self):
        kind, lib, h = self._impl
        if kind == "closed":
            raise StopIteration
        if kind == "python":
            if self.block_shuffle:
                raw = lib.next_view()
                return raw.view(self.dtype).reshape(
                    (self.batch_size,) + self.record_shape)
            out = self._pool.acquire()
            try:
                lib.next_into(out)
            except StopIteration:
                self._pool.release(out)
                raise
            return out
        if self.block_shuffle:
            return self._next_view(lib, h)
        if self._ring_depth:
            # Top the ring up BEFORE collecting: the queued assemblies
            # overlap both this wait and the consumer's downstream work.
            while len(self._ring) < self._ring_depth:
                buf = self._pool.acquire()
                if lib.loader_next_async(
                        h, buf.ctypes.data_as(ctypes.c_void_p)) != 0:
                    # Ring refused (full/busy — misuse or shared handle):
                    # degrade to the synchronous path for this batch.
                    self._pool.release(buf)
                    break
                self._ring.append(buf)
            if self._ring:
                rc = lib.loader_next_wait(h)
                buf = self._ring.popleft()
                if rc != 0:
                    self._pool.release(buf)
                    self._drain_ring()
                    raise StopIteration
                return buf
            # fall through: synchronous degrade path
        out = self._pool.acquire()
        rc = lib.loader_next(h, out.ctypes.data_as(ctypes.c_void_p))
        if rc != 0:
            self._pool.release(out)
            raise StopIteration
        return out

    def _drain_ring(self):
        """Settle every queued async assembly (their thread writes into
        buffers Python owns) and reclaim the buffers."""
        kind, lib, h = self._impl
        while self._ring:
            if kind == "native":
                lib.loader_next_wait(h)
            self._pool.release(self._ring.popleft())

    def close(self):
        kind, lib, h = self._impl
        if kind == "native" and h:
            self._drain_ring()
            lib.loader_destroy(h)
            self._impl = ("closed", None, None)
        elif kind == "python":
            lib.close()
            self._impl = ("closed", None, None)

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


class _PyLoaderImpl:
    """Threaded pure-Python fallback with the same shuffle semantics."""

    def __init__(self, path, sample_bytes, batch_size, seed, capacity,
                 shard_index=0, shard_count=1, block_shuffle=False):
        data = np.memmap(path, np.uint8, "r")
        file_samples = data.size // sample_bytes
        per = file_samples // shard_count
        self._lo = shard_index * per
        self.num_samples = per
        if self.num_samples < batch_size:
            raise ValueError(f"shard has {per} records < batch {batch_size}")
        self._data = data[:file_samples * sample_bytes].reshape(
            file_samples, sample_bytes)
        self._batch = batch_size
        self._seed = seed
        self._block = block_shuffle
        self._reads = 0
        self._min = -1
        self._max = -1
        self._stats_lock = threading.Lock()
        if block_shuffle:
            self._ticket = 0  # synchronous: views need no producer thread
            return
        self._q = queue.Queue(maxsize=capacity)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _account(self, lo, hi, count):
        with self._stats_lock:
            self._reads += count
            if self._min < 0 or lo < self._min:
                self._min = lo
            if hi > self._max:
                self._max = hi

    def stats(self):
        with self._stats_lock:
            return self._reads, self._min, self._max

    def _loop(self):
        epoch = 0
        while not self._stop.is_set():
            rng = np.random.RandomState((self._seed + epoch) % (2 ** 31))
            perm = self._lo + rng.permutation(self.num_samples)
            for s in range(self.num_samples // self._batch):
                idx = perm[s * self._batch:(s + 1) * self._batch]
                batch = np.asarray(self._data[idx])
                self._account(int(idx.min()), int(idx.max()), len(idx))
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            epoch += 1

    def next_into(self, out):
        # Timeout-and-check: after close() the producer stops feeding the
        # queue, so a bare blocking get() would hang the consumer forever
        # (regression: shutdown hang).  StopIteration mirrors the native
        # loader's post-close contract.
        while True:
            try:
                batch = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stop.is_set():
                    raise StopIteration from None
        out.view(np.uint8).reshape(batch.shape)[:] = batch

    def next_view(self):
        """Zero-copy block hand-out (block-shuffle mode only)."""
        bpe = self.num_samples // self._batch
        epoch, slot = divmod(self._ticket, bpe)
        self._ticket += 1
        rng = np.random.RandomState((self._seed + epoch) % (2 ** 31))
        block = int(rng.permutation(bpe)[slot])
        first = self._lo + block * self._batch
        self._account(first, first + self._batch - 1, self._batch)
        out = self._data[first:first + self._batch]
        out.flags.writeable = False
        return out

    def close(self):
        if not self._block:
            self._stop.set()


class BlockStacker:
    """Stacks K consecutive host batches into one ``(K,) + batch`` block.

    Feeds the Runner's fused multi-step ("megastep") dispatch: one block
    is ONE XLA dispatch of K training steps (``Runner.run(unroll=K)``,
    docs/usage/performance.md).  Blocks are assembled into a small
    :class:`BufferPool` of reusable block-shaped staging buffers
    (``np.stack(..., out=pool_buffer)``), and each source batch buffer is
    recycled back to ``recycle_to`` (the wrapped loader) as soon as its
    rows are copied — the loader's pool keeps cycling at batch
    granularity while blocks cycle at block granularity.

    Pass this object as the :class:`DevicePrefetcher`'s ``loader=`` so a
    settled block's staging buffer returns here (:meth:`recycle` routes
    block-shaped buffers to the block pools and everything else to the
    inner loader, which ignores what it does not own).
    """

    def __init__(self, iterator, unroll, recycle_to=None, pool_size=None):
        if unroll < 1:
            raise ValueError(f"unroll must be >= 1, got {unroll}")
        self._it = iter(iterator)
        self._k = int(unroll)
        self._recycle_to = recycle_to
        if pool_size is None:
            pool_size = PREFETCH_DEPTH + 2
        self._pool_size = max(1, int(pool_size))
        self._pools = {}  # (shape, dtype) -> BufferPool of block buffers

    @property
    def unroll(self):
        return self._k

    def recycle(self, buf):
        """Return a block buffer to its pool; foreign arrays fall through
        to the wrapped loader's pool (which ignores what it cannot reuse)."""
        for pool in self._pools.values():
            if pool.release(buf):
                return
        if self._recycle_to is not None:
            self._recycle_to.recycle(buf)

    def _block_buffer(self, shape, dtype):
        key = (tuple(shape), np.dtype(dtype))
        pool = self._pools.get(key)
        if pool is None:
            pool = BufferPool(shape, dtype, self._pool_size)
            self._pools[key] = pool
        return pool.acquire()

    def __iter__(self):
        return self

    def __next__(self):
        batches = []
        try:
            for _ in range(self._k):
                batches.append(next(self._it))
        except StopIteration:
            # Partial block at end-of-stream: recycle what was pulled and
            # end cleanly (a megastep needs exactly K steps of data).
            if self._recycle_to is not None:
                for b in batches:
                    for leaf in jax.tree_util.tree_leaves(b):
                        self._recycle_to.recycle(leaf)
            raise
        flat = [jax.tree_util.tree_flatten(b) for b in batches]
        treedef = flat[0][1]
        out = []
        for j, first in enumerate(flat[0][0]):
            parts = [np.asarray(f[0][j]) for f in flat]
            buf = self._block_buffer((self._k,) + parts[0].shape,
                                     parts[0].dtype)
            np.stack(parts, out=buf)
            out.append(buf)
        if self._recycle_to is not None:
            for b in batches:
                for leaf in jax.tree_util.tree_leaves(b):
                    self._recycle_to.recycle(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)


class DevicePrefetcher:
    """Keeps ``depth`` mesh-sharded batches in flight ahead of the consumer.

    Wraps any host-batch iterator; one code path for every host/backed
    combination (replacing the previous threaded / pipelined-single-core /
    passthrough trio): a deque of up to ``depth`` in-flight transfers with
    explicit completion handles.  Each ``__next__``:

    1. tops the deque up — pulls host batches and *issues* their transfers
       (``shard_batch`` returns without waiting);
    2. settles the oldest just-in-time (``block_until_ready``), recording
       the wait as *data-wait time* (:meth:`stats`; the Runner surfaces it
       as the ``step.data_wait_ms`` metric);
    3. recycles the settled batch's staging buffers back to the loader's
       :class:`BufferPool` (``loader=``), and hands the device batch out.

    Transfers are issued at the start of the ``__next__`` call — after
    the consumer dispatched the previous step — and every handed-out batch
    is settled, so the wire time of the queued transfers overlaps device
    execution.

    On multi-core hosts a pull thread drains the upstream iterator into a
    bounded queue so batch assembly overlaps the consumer; transfers are
    always issued from the consumer thread.

    ``depth=0`` degrades to synchronous shard-settle-handout (no
    overlap), kept for debugging and as the safe fallback.
    """

    def __init__(self, iterator, remapper, depth=None,
                 shard_in_background=None, loader=None,
                 pull_in_background=None, shard_fn=None):
        if depth is None:
            depth = PREFETCH_DEPTH
        # A source exposing ``next_nowait()`` (returning None when nothing
        # is ready RIGHT NOW) opts into lazy top-up: the window fills
        # opportunistically instead of blocking until ``depth`` batches
        # exist.  The serve request queue uses this — a latency-sensitive
        # consumer must never stall waiting for traffic that hasn't
        # arrived — while training iterators keep the fill-to-depth
        # behavior.
        self._next_nowait = getattr(iterator, "next_nowait", None)
        self._it = iter(iterator)
        self._remapper = remapper
        # ``shard_fn`` overrides the placement call (same signature as
        # ``Remapper.shard_batch``): ``shard_block`` feeds
        # K-stacked megastep blocks through the same depth-N machinery.
        self._shard = shard_fn if shard_fn is not None \
            else remapper.shard_batch
        self._loader = loader
        self._depth = depth
        self._inflight = deque()  # (device_batch, host_batch)
        self._exhausted = False
        self._wait_s_total = 0.0
        self._wait_s_last = 0.0
        self._batches = 0
        self._obs = observability if observability.enabled() else None
        # ``shard_in_background`` is legacy (sharding now always happens
        # on the consumer thread); a truthy value still requests the pull
        # thread it used to imply.
        if pull_in_background is None:
            pull_in_background = bool(shard_in_background) or \
                (os.cpu_count() or 1) > 1
        self._q = None
        if pull_in_background and depth > 0:
            self._q = queue.Queue(maxsize=max(1, depth))
            self._done = object()
            self._thread = threading.Thread(target=self._pull_loop,
                                            daemon=True)
            self._thread.start()

    # -- source side ---------------------------------------------------------

    def _pull_loop(self):
        try:
            for batch in self._it:
                self._q.put(batch)
        except Exception as e:  # noqa: BLE001 - surfaced on next()
            self._q.put(e)
        self._q.put(self._done)

    def _pull(self):
        """Next host batch; raises StopIteration when the source ends."""
        if self._q is None:
            return next(self._it)
        item = self._q.get()
        if item is self._done:
            raise StopIteration
        if isinstance(item, Exception):
            raise item
        return item

    # -- transfer side -------------------------------------------------------

    def _settle(self, device_batch):
        """Block until the batch's transfers completed; the wait is
        ``autodist.data_wait`` in a profiler trace."""
        obs = self._obs
        t0 = time.perf_counter()
        if obs is None:
            jax.block_until_ready(device_batch)
        else:
            with obs.annotate("data_wait"):
                jax.block_until_ready(device_batch)
        dt = time.perf_counter() - t0
        self._wait_s_last = dt
        self._wait_s_total += dt
        self._batches += 1

    def _recycle(self, host_batch):
        """Hand staging buffers back to the loader pool once the transfer
        retired.  Skipped on backends whose device_put may ALIAS the host
        buffer (CPU zero-copy): there, reusing the buffer would corrupt
        live device arrays; the pool degrades to fresh allocations."""
        if self._loader is None:
            return
        from autodist_tpu.remapper import transfers_copy_host_buffer
        if not transfers_copy_host_buffer():
            return
        for leaf in jax.tree_util.tree_leaves(host_batch):
            self._loader.recycle(leaf)

    @property
    def last_wait_ms(self):
        return self._wait_s_last * 1e3

    def stats(self):
        """Cumulative data-wait accounting for bench/telemetry."""
        return {"batches": self._batches,
                "data_wait_ms_total": round(self._wait_s_total * 1e3, 3),
                "data_wait_ms_mean": round(
                    self._wait_s_total * 1e3 / self._batches, 3)
                if self._batches else None,
                "inflight": len(self._inflight)}

    def __iter__(self):
        return self

    def __next__(self):
        if self._depth == 0:
            batch = self._shard(self._pull())
            self._settle(batch)
            return batch
        # Issue phase (post-dispatch position: the consumer dispatched the
        # previous step before calling in): top the in-flight window up.
        while len(self._inflight) < self._depth and not self._exhausted:
            lazy = self._next_nowait is not None and self._inflight
            try:
                hb = self._next_nowait() if lazy else self._pull()
            except StopIteration:
                self._exhausted = True
                break
            if hb is None and lazy:
                break  # nothing queued right now; don't stall the window
            db = self._shard(hb)
            self._inflight.append((db, hb))
        if not self._inflight:
            raise StopIteration
        db, hb = self._inflight.popleft()
        self._settle(db)
        self._recycle(hb)
        return db
