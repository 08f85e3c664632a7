"""Native (C++) data loader + device prefetcher: buffer pool, async
assembly ring, per-host sharding, zero-copy block shuffle, depth-N device
prefetch."""
import threading

import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.data import (BufferPool, DevicePrefetcher, NativeDataLoader,
                               write_record_file)
from autodist_tpu.models import mlp
from autodist_tpu.strategy import AllReduce


@pytest.fixture
def record_file(tmp_path):
    rng = np.random.RandomState(0)
    data = rng.randn(64, 16).astype(np.float32)
    path = tmp_path / "records.bin"
    write_record_file(path, data)
    return path, data


def _row_sums(x):
    return np.sort(x.sum(1))


# -- basic contracts ---------------------------------------------------------


def test_native_backend_compiles_and_loads(record_file):
    path, data = record_file
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=3)
    assert loader.backend == "native", "g++ toolchain expected in this image"
    assert loader.num_samples == 64
    batches = [next(loader) for _ in range(8)]  # exactly one epoch
    loader.close()
    got = np.concatenate(batches)
    assert got.shape == (64, 16)
    # One epoch is a permutation of the data: same multiset of rows.
    np.testing.assert_allclose(_row_sums(got), _row_sums(data), rtol=1e-6)


def test_epochs_reshuffle(record_file):
    path, _ = record_file
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=64, seed=5)
    e0 = next(loader).copy()
    e1 = next(loader).copy()
    loader.close()
    assert not np.array_equal(e0, e1), "epochs should reshuffle"
    np.testing.assert_allclose(_row_sums(e0), _row_sums(e1), rtol=1e-6)


def test_multithreaded_delivery_is_ticket_ordered(record_file):
    """With num_threads>1, batches must still arrive in epoch order: each
    window of batches_per_epoch consecutive batches is one full permutation
    (regression: workers used to push in completion order, letting epoch
    N+1 batches land inside epoch N)."""
    path, data = record_file
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=7,
                              num_threads=4, capacity=3)
    assert loader.backend == "native"
    want = _row_sums(data)
    for _ in range(3):  # three consecutive epochs, each a full permutation
        got = np.concatenate([next(loader) for _ in range(8)])
        np.testing.assert_allclose(_row_sums(got), want, rtol=1e-6)
    loader.close()


def test_epoch_reshuffle_deterministic_per_seed(record_file):
    """Same seed => identical batch sequence across loader instances, INTO
    and ACROSS the epoch boundary; different seed => different order."""
    path, _ = record_file
    seqs = {}
    for seed in (9, 9, 10):
        loader = NativeDataLoader(path, (16,), np.float32, batch_size=8,
                                  seed=seed, pipeline=False)
        seq = [next(loader).copy() for _ in range(20)]  # 2.5 epochs
        loader.close()
        seqs.setdefault(seed, []).append(seq)
    a, b = seqs[9]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y)
               for x, y in zip(a, seqs[10][0])), "seeds must differ"


def test_python_fallback_matches_contract(record_file, monkeypatch):
    path, data = record_file
    import autodist_tpu.data.loader as loader_mod
    monkeypatch.setattr(loader_mod, "_lib", None)
    monkeypatch.setattr(loader_mod, "_lib_err", RuntimeError("forced"))
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=3)
    assert loader.backend == "python"
    got = np.concatenate([next(loader) for _ in range(8)])
    loader.close()
    np.testing.assert_allclose(_row_sums(got), _row_sums(data), rtol=1e-6)


def test_native_python_parity_on_same_record_file(record_file, monkeypatch):
    """Both backends over the SAME file must agree on the full contract:
    stripe size, per-epoch row multiset, batch geometry, read accounting
    (they need not agree on the permutation order — different RNGs)."""
    path, data = record_file
    import autodist_tpu.data.loader as loader_mod
    kwargs = dict(batch_size=8, seed=3, shard_index=1, shard_count=2,
                  pipeline=False)
    nat = NativeDataLoader(path, (16,), np.float32, **kwargs)
    assert nat.backend == "native"
    nat_rows = np.concatenate([next(nat) for _ in range(4)])
    nat_stats = nat.stats()
    nat_n = nat.num_samples
    nat.close()

    monkeypatch.setattr(loader_mod, "_lib", None)
    monkeypatch.setattr(loader_mod, "_lib_err", RuntimeError("forced"))
    py = NativeDataLoader(path, (16,), np.float32, **kwargs)
    assert py.backend == "python"
    py_rows = np.concatenate([next(py) for _ in range(4)])
    py_stats = py.stats()
    assert py.num_samples == nat_n == 32
    py.close()

    np.testing.assert_allclose(_row_sums(nat_rows), _row_sums(py_rows),
                               rtol=1e-6)
    np.testing.assert_allclose(_row_sums(nat_rows), _row_sums(data[32:]),
                               rtol=1e-6)
    for s in (nat_stats, py_stats):
        # records_read counts records TOUCHED — read-ahead (python
        # producer thread / native ring) may run past what was consumed,
        # but never outside the stripe.
        assert s["records_read"] >= 32
        assert s["min_index"] >= 32 and s["max_index"] <= 63


# -- buffer pool + async assembly ring --------------------------------------


def test_buffer_pool_acquire_release_fallback():
    pool = BufferPool((4, 8), np.float32, size=2)
    a, b = pool.acquire(), pool.acquire()
    assert pool.fallback_allocs == 0
    c = pool.acquire()  # beyond size: degrades to a fresh alloc
    assert pool.fallback_allocs == 1
    assert pool.release(a) and pool.release(b)
    assert pool.acquire() is b and pool.acquire() is a  # LIFO reuse
    # Foreign arrays are ignored, never pooled.
    assert not pool.release(np.zeros((3, 3)))
    assert not pool.release(c[:2])  # view: not owndata
    assert not pool.release("not an array")


def test_ring_matches_sync_sequence(record_file):
    """The multi-slot async assembly ring (``pipeline=True``) must hand out
    the exact batch sequence of the synchronous mode — same tickets, same
    per-epoch shuffle — across epoch boundaries, at any depth."""
    path, _ = record_file
    sync = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=11,
                            num_threads=0, pipeline=False)
    for depth in (1, 3):
        ring = NativeDataLoader(path, (16,), np.float32, batch_size=8,
                                seed=11, num_threads=0, pipeline=True,
                                ring_depth=depth)
        for _ in range(20):  # 2.5 epochs of 8 batches
            a, b = next(sync), next(ring)
            np.testing.assert_array_equal(a, b)
            sync.recycle(a)
            ring.recycle(b)
        assert ring.stats()["pool_fallback_allocs"] == 0
        ring.close()
        sync.close()
        sync = NativeDataLoader(path, (16,), np.float32, batch_size=8,
                                seed=11, num_threads=0, pipeline=False)
    sync.close()


def test_ring_degrades_to_sync_when_async_refused(record_file):
    """When the native ring refuses a job (-2: full/busy), __next__ must
    fall back to the synchronous path and keep the sequence intact."""
    path, _ = record_file

    class _NoAsync:
        """lib proxy whose async ring is permanently busy."""

        def __init__(self, lib):
            self._lib = lib

        def __getattr__(self, name):
            return getattr(self._lib, name)

        def loader_next_async(self, h, buf):
            return -2

    ref = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=4,
                           num_threads=0, pipeline=False)
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=4,
                              num_threads=0, pipeline=True)
    assert loader._ring_depth > 0
    kind, lib, h = loader._impl
    loader._impl = (kind, _NoAsync(lib), h)
    for _ in range(12):
        np.testing.assert_array_equal(next(ref), next(loader))
    assert not loader._ring, "refused jobs must not enter the ring"
    loader.close()
    ref.close()


def test_close_with_inflight_ring_assemblies(record_file):
    """close() must drain every queued async assembly before destroying the
    native loader (its thread writes into buffers Python owns)."""
    path, _ = record_file
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=2,
                              num_threads=0, pipeline=True, ring_depth=3)
    next(loader)  # tops the ring up to 3, then collects the oldest
    assert len(loader._ring) == 2
    loader.close()  # must not crash, hang, or leak the in-flight jobs
    with pytest.raises(StopIteration):
        next(loader)


def test_py_loader_close_does_not_hang_consumer(record_file):
    """Regression: _PyLoaderImpl.next_into blocked forever on an empty
    queue after close() set _stop; the timeout-and-check loop must raise
    StopIteration instead, and a post-close __next__ raises immediately."""
    path, _ = record_file
    from autodist_tpu.data.loader import _PyLoaderImpl
    impl = _PyLoaderImpl(path, 64, 8, seed=0, capacity=4)
    impl.close()
    done = []

    def drain():
        out = np.empty((8, 64), np.uint8)
        try:
            while True:
                impl.next_into(out)
        except StopIteration:
            done.append(True)

    t = threading.Thread(target=drain, daemon=True)
    t.start()
    t.join(timeout=10)
    assert done == [True], "next_into hung after close()"


# -- per-host sharded loading ------------------------------------------------


def test_sharded_stripes_are_disjoint_and_accounted(record_file):
    path, data = record_file
    loaders = [NativeDataLoader(path, (16,), np.float32, batch_size=8,
                                seed=1, shard_index=i, shard_count=2,
                                pipeline=False)
               for i in range(2)]
    assert all(ld.num_samples == 32 for ld in loaders)
    stripes = [np.concatenate([next(ld) for _ in range(4)])
               for ld in loaders]
    # Each shard sees exactly its contiguous stripe of the file, nothing
    # else — asserted by content AND by read accounting.
    np.testing.assert_allclose(_row_sums(stripes[0]), _row_sums(data[:32]),
                               rtol=1e-6)
    np.testing.assert_allclose(_row_sums(stripes[1]), _row_sums(data[32:]),
                               rtol=1e-6)
    s0, s1 = (ld.stats() for ld in loaders)
    assert s0["min_index"] == 0 and s0["max_index"] == 31
    assert s1["min_index"] == 32 and s1["max_index"] == 63
    for ld in loaders:
        ld.close()


def test_per_host_resolves_from_process_env(record_file):
    """per_host=True on a single process is the identity stripe."""
    path, _ = record_file
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8,
                              per_host=True)
    assert (loader.shard_index, loader.shard_count) == (0, 1)
    assert loader.num_samples == 64
    loader.close()


def test_shard_local_batch_matches_shard_batch(record_file):
    """Single-process equivalence: the per-host assembly path
    (make_array_from_single_device_arrays over per-device local shards)
    must produce BITWISE the same global arrays as the plain path."""
    import jax
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    ref = runner.remapper.shard_batch(batch)
    local = runner.remapper.shard_local_batch(batch)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(local)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # And it trains.
    state = runner.create_state()
    state, metrics = runner.step(state, local, shard_inputs=False)
    assert np.isfinite(float(metrics["loss"]))


# -- zero-copy block shuffle -------------------------------------------------


def test_block_shuffle_zero_copy_views(record_file):
    path, data = record_file
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=5,
                              block_shuffle=True)
    views = [next(loader) for _ in range(8)]
    got = np.concatenate(views)
    # Zero-copy: read-only views, no owned allocation per batch.
    assert all(not v.flags.writeable and not v.flags.owndata for v in views)
    np.testing.assert_allclose(_row_sums(got), _row_sums(data), rtol=1e-6)
    # Records inside a block keep file order (the documented granularity
    # trade): every batch is a contiguous run of the file.
    for v in views:
        idx = int(np.abs(data - v[0]).sum(1).argmin())
        np.testing.assert_allclose(v, data[idx:idx + 8], rtol=1e-6)
    # Epochs reshuffle blocks deterministically per seed.
    e1 = np.concatenate([next(loader) for _ in range(8)])
    assert not np.array_equal(got, e1)
    np.testing.assert_allclose(_row_sums(e1), _row_sums(data), rtol=1e-6)
    st = loader.stats()
    assert st["records_read"] == 128
    loader.close()

    again = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=5,
                             block_shuffle=True)
    np.testing.assert_array_equal(next(again), views[0])
    again.close()


def test_block_shuffle_python_fallback_parity(record_file, monkeypatch):
    path, data = record_file
    import autodist_tpu.data.loader as loader_mod
    monkeypatch.setattr(loader_mod, "_lib", None)
    monkeypatch.setattr(loader_mod, "_lib_err", RuntimeError("forced"))
    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=5,
                              block_shuffle=True)
    assert loader.backend == "python"
    views = [next(loader) for _ in range(8)]
    got = np.concatenate(views)
    assert all(not v.flags.writeable for v in views)
    np.testing.assert_allclose(_row_sums(got), _row_sums(data), rtol=1e-6)
    loader.close()


# -- device prefetcher -------------------------------------------------------


def test_device_prefetcher_feeds_training(record_file):
    path, _ = record_file
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()

    loader = NativeDataLoader(path, (16,), np.float32, batch_size=8, seed=0)
    rng = np.random.RandomState(1)

    def batches():
        for _ in range(5):
            x = next(loader)
            yield (x, rng.randint(0, 4, (8,)).astype(np.int32))

    feed = DevicePrefetcher(batches(), runner.remapper, loader=loader)
    n = 0
    for b in feed:
        state, metrics = runner.step(state, b, shard_inputs=False)
        n += 1
    loader.close()
    assert n == 5
    assert np.isfinite(float(metrics["loss"]))
    stats = feed.stats()
    assert stats["batches"] == 5
    assert stats["data_wait_ms_total"] >= 0


@pytest.mark.parametrize("depth", [0, 1, 3])
def test_device_prefetcher_depths_deliver_all_batches(record_file, depth):
    """Every depth (passthrough, single, multi) delivers every batch exactly
    once, in order, with a clean StopIteration."""
    path, data = record_file
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)

    rng = np.random.RandomState(1)
    xs = [data[i * 8:(i + 1) * 8] for i in range(4)]
    feed = DevicePrefetcher(
        ((x, rng.randint(0, 4, (8,)).astype(np.int32)) for x in xs),
        runner.remapper, depth=depth, pull_in_background=False)
    got = list(feed)
    assert len(got) == 4
    for x, b in zip(xs, got):
        np.testing.assert_allclose(np.asarray(b[0]), x, rtol=1e-6)
    with pytest.raises(StopIteration):
        next(feed)


def test_device_prefetcher_issues_transfers_without_blocking(record_file):
    """depth>=1 issues the whole window of transfers before the first
    hand-out (shard_batch returns without waiting) and settles each batch
    before it leaves."""
    path, data = record_file
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)

    calls = []
    orig = runner.remapper.shard_batch

    def spy(b):
        calls.append(1)
        return orig(b)
    runner.remapper.shard_batch = spy

    rng = np.random.RandomState(1)
    xs = [data[i * 8:(i + 1) * 8] for i in range(4)]
    feed = DevicePrefetcher(
        ((x, rng.randint(0, 4, (8,)).astype(np.int32)) for x in xs),
        runner.remapper, depth=2, pull_in_background=False)
    first = next(feed)
    # Both in-flight transfers were issued before the first batch came out.
    assert len(calls) == 2
    got = [first] + list(feed)
    assert len(got) == 4 and len(calls) == 4
    # Delivery preserves order and content.
    for x, b in zip(xs, got):
        np.testing.assert_allclose(np.asarray(b[0]), x, rtol=1e-6)
    assert feed.stats()["batches"] == 4


def test_device_prefetcher_background_pull(record_file):
    """The pull thread drains the upstream iterator without dropping,
    reordering, or swallowing its terminal StopIteration."""
    path, data = record_file
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    rng = np.random.RandomState(1)
    xs = [data[i * 8:(i + 1) * 8] for i in range(6)]
    feed = DevicePrefetcher(
        ((x, rng.randint(0, 4, (8,)).astype(np.int32)) for x in xs),
        runner.remapper, depth=2, pull_in_background=True)
    got = list(feed)
    assert len(got) == 6
    for x, b in zip(xs, got):
        np.testing.assert_allclose(np.asarray(b[0]), x, rtol=1e-6)


def test_device_prefetcher_surfaces_iterator_errors(record_file):
    path, data = record_file
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)

    def bad():
        yield (data[:8], np.zeros((8,), np.int32))
        raise RuntimeError("boom")

    feed = DevicePrefetcher(bad(), runner.remapper, depth=1,
                            pull_in_background=True)
    next(feed)
    with pytest.raises(RuntimeError, match="boom"):
        next(feed)


def test_shard_batch_returns_live_arrays():
    import jax
    params, loss_fn, batch = mlp.tiny_fixture()
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=batch)
    runner = ad.create_distributed_session(item)
    out = runner.remapper.shard_batch(batch)
    leaves = jax.tree_util.tree_leaves(out)
    assert all(isinstance(l, jax.Array) for l in leaves)
    jax.block_until_ready(leaves)
    np.testing.assert_allclose(np.asarray(out[0]), batch[0], rtol=1e-6)
