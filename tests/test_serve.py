"""Serving runtime (ISSUE 6): bucket selection, continuous-batching
semantics, never-donated params, multi-replica dispatch, the
serve_latency tuner objective, and the end-to-end acceptance
test on a models-zoo model."""
import threading
import time

import numpy as np
import jax
import pytest

from autodist_tpu import observability, serve
from autodist_tpu.models import mlp
from autodist_tpu.serve.buckets import normalize_buckets, pick_bucket


# -- fixtures ----------------------------------------------------------------


CFG = mlp.MLPConfig(in_dim=16, hidden=(32,), num_classes=4)


def _apply(p, x):
    return mlp.apply(p, CFG, x)


def _fixture(seed=0):
    params = mlp.init(jax.random.PRNGKey(seed), CFG)
    rng = np.random.RandomState(seed)
    example = rng.randn(8, 16).astype(np.float32)
    return params, example, rng


_direct_apply = jax.jit(_apply)


def _assert_matches_direct_apply(served, params, x):
    """A served answer against a direct ``apply`` of the unpadded rows.

    The two are different executables (the bucket's padded batch, sharded
    over the mesh, against ``x``'s own shape on one device) and XLA's
    reduction order follows the shape, so they agree to rounding, not
    bitwise: one ulp observed (max abs 2.98e-07, 15 of 24 elements).
    Bitwise is the engine's promise where ONE executable answers the same
    rows twice; the tests that claim it use ``assert_array_equal``."""
    np.testing.assert_allclose(
        np.asarray(served), np.asarray(_direct_apply(params, x)),
        rtol=2e-6, atol=1e-6)


@pytest.fixture(autouse=True)
def _fresh_metrics():
    observability.reset()
    yield
    observability.reset()


# -- pick_bucket (public helper; paddings-machinery satellite) ---------------


def test_pick_bucket_exact_fit():
    assert pick_bucket(8, [8, 32]) == (8,)
    assert pick_bucket((32,), [8, 32]) == (32,)
    assert pick_bucket((4, 128), [(4, 128), (16, 128)]) == (4, 128)


def test_pick_bucket_smallest_admissible():
    assert pick_bucket(3, [32, 8, 128]) == (8,)
    assert pick_bucket(9, [32, 8, 128]) == (32,)
    # multi-dim: fewest padded elements wins, not first listed
    assert pick_bucket((3, 100), [(8, 256), (4, 128)]) == (4, 128)


def test_pick_bucket_oversize_is_an_error():
    with pytest.raises(ValueError, match="exceeds every bucket"):
        pick_bucket(129, [8, 32, 128])
    with pytest.raises(ValueError, match="exceeds every bucket"):
        pick_bucket((4, 300), [(8, 256)])


def test_pick_bucket_empty_and_malformed_buckets():
    with pytest.raises(ValueError, match="empty bucket list"):
        pick_bucket(4, [])
    with pytest.raises(ValueError, match="positive"):
        pick_bucket(4, [0, 8])
    with pytest.raises(ValueError, match="rank"):
        pick_bucket(4, [(8, 128), 32])
    with pytest.raises(ValueError, match="ranks"):
        pick_bucket((4, 128), [8, 32])


def test_normalize_buckets_sorts_and_dedups():
    assert normalize_buckets([128, 8, 32, 8]) == [(8,), (32,), (128,)]


def test_buckets_from_env(monkeypatch):
    monkeypatch.setenv("AUTODIST_SERVE_BUCKETS", "32,8, 128")
    assert serve.buckets_from_env() == [(8,), (32,), (128,)]
    monkeypatch.setenv("AUTODIST_SERVE_BUCKETS", "8x128,32x128")
    assert serve.buckets_from_env() == [(8, 128), (32, 128)]
    monkeypatch.delenv("AUTODIST_SERVE_BUCKETS")
    assert serve.buckets_from_env((4,)) == [(4,)]


# -- continuous batching semantics -------------------------------------------


def test_lone_request_not_starved_by_max_wait():
    """A single queued request must dispatch once its max-wait deadline
    passes — coalescing may delay, never starve."""
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(8, 32),
                      max_wait_ms=50) as srv:
        x = rng.randn(2, 16).astype(np.float32)
        t0 = time.perf_counter()
        out = srv.submit(x).result(timeout=10)
        dt = time.perf_counter() - t0
        assert out.shape == (2, 4)
        # Generous ceiling (CI hosts stall): the point is "seconds, not
        # forever"; the deadline itself is 50ms.
        assert dt < 8.0
        assert srv.stats()["batches"] == 1


def test_fifo_coalescing_and_exact_depadding():
    """Requests submitted back-to-back coalesce into ONE bucket, pack in
    FIFO order, and de-pad to exactly the requested rows."""
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(8, 32),
                      max_wait_ms=300) as srv:
        inputs = [rng.randn(r, 16).astype(np.float32) for r in (3, 5, 2, 6)]
        futs = [srv.submit(x) for x in inputs]
        ref = jax.jit(_apply)
        for x, f in zip(inputs, futs):
            out = np.asarray(f.result(timeout=30))
            assert out.shape == (x.shape[0], 4)  # exactly the asked rows
            np.testing.assert_array_equal(out, np.asarray(ref(params, x)))
        st = srv.stats()
        assert st["batches"] == 1, "16 rows over 4 requests should ride " \
            "one bucket under a 300ms coalesce window"
        # FIFO within the bucket: row assignments are contiguous and in
        # submission (seq) order.
        asg = srv.last_dispatch["assignments"]
        assert [seq for seq, _, _ in asg] == sorted(seq for seq, _, _ in asg)
        lo = 0
        for (_, a, b), x in zip(asg, inputs):
            assert (a, b) == (lo, lo + x.shape[0])
            lo = b
        assert srv.last_dispatch["bucket"] == 32  # smallest admissible > 16
        assert st["padded_rows"] == 32 - 16


def test_oversize_and_malformed_requests_rejected_at_submit():
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(8,),
                      max_wait_ms=1) as srv:
        with pytest.raises(ValueError, match="exceeds every bucket"):
            srv.submit(rng.randn(9, 16).astype(np.float32))
        with pytest.raises(ValueError, match="trailing dims"):
            srv.submit(rng.randn(4, 17).astype(np.float32))
        with pytest.raises(ValueError, match="empty request"):
            srv.submit(rng.randn(0, 16).astype(np.float32))
        # The server survives rejections: a good request still works.
        assert srv.infer(rng.randn(4, 16).astype(np.float32),
                         timeout=30).shape == (4, 4)


def test_request_larger_than_current_group_starts_next_bucket():
    """A request that would overflow the largest bucket dispatches the
    open group and seeds the next one — nothing is dropped."""
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(8,),
                      max_wait_ms=200) as srv:
        a = rng.randn(6, 16).astype(np.float32)
        b = rng.randn(5, 16).astype(np.float32)  # 6 + 5 > 8: splits
        fa, fb = srv.submit(a), srv.submit(b)
        out_a, out_b = np.asarray(fa.result(30)), np.asarray(fb.result(30))
        _assert_matches_direct_apply(out_a, params, a)
        _assert_matches_direct_apply(out_b, params, b)
        assert srv.stats()["batches"] == 2
        # The same rows through the same bucket executable again: bitwise.
        np.testing.assert_array_equal(np.asarray(srv.infer(a, timeout=30)),
                                      out_a)
        np.testing.assert_array_equal(np.asarray(srv.infer(b, timeout=30)),
                                      out_b)
        assert srv.stats()["batches"] == 4


# -- never-donated params (remapper satellite) -------------------------------


def test_serve_never_donates_params_bitwise_across_buckets():
    """The dispatch path must never donate the placed params: a second
    identical request — including one that routes through a DIFFERENT
    bucket executable in between — must answer bitwise-identically, and
    the param buffers must stay live."""
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(8, 32),
                      max_wait_ms=1) as srv:
        x = rng.randn(5, 16).astype(np.float32)
        first = np.asarray(srv.infer(x, timeout=30))          # bucket 8
        big = rng.randn(20, 16).astype(np.float32)
        srv.infer(big, timeout=30)                            # bucket 32
        second = np.asarray(srv.infer(x, timeout=30))         # bucket 8 again
        np.testing.assert_array_equal(first, second)
        for rep in srv.engine.replicas:
            for leaf in jax.tree_util.tree_leaves(rep.params):
                assert isinstance(leaf, jax.Array)
                assert not leaf.is_deleted(), \
                    "serve dispatch donated a parameter buffer"


def test_serve_remapper_resident_fast_path():
    """A re-used request buffer that is already a committed device array
    with the target sharding must pass through ``shard_batch`` untouched
    (leaf identity) — the resident fast path on the serve remapper."""
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(8,),
                      max_wait_ms=1) as srv:
        rep = srv.engine.replicas[0]
        host = rng.randn(8, 16).astype(np.float32)
        placed = rep.remapper.shard_batch(host)
        again = rep.remapper.shard_batch(placed)
        assert again is placed  # no device_put tree work on re-use


# -- multi-replica dispatch --------------------------------------------------


def test_multi_replica_least_loaded_dispatch():
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(4, 8),
                      max_wait_ms=1, replicas=2) as srv:
        assert len(srv.engine.replicas) == 2
        meshes = [rep.program.mesh for rep in srv.engine.replicas]
        assert meshes[0].devices.size == meshes[1].devices.size == 4
        assert not (set(meshes[0].devices.flat) &
                    set(meshes[1].devices.flat))
        ref = jax.jit(_apply)
        inputs = [rng.randn(4, 16).astype(np.float32) for _ in range(8)]
        futs = [srv.submit(x) for x in inputs]
        for x, f in zip(inputs, futs):
            np.testing.assert_array_equal(np.asarray(f.result(30)),
                                          np.asarray(ref(params, x)))
        st = srv.stats()
        dispatches = [r["dispatches"] for r in st["replicas"]]
        assert sum(dispatches) == st["batches"]
        assert all(d > 0 for d in dispatches), \
            f"least-loaded scheduler starved a replica: {dispatches}"


def test_multi_replica_rejects_model_parallel_strategy():
    from autodist_tpu.strategy import ModelParallel, AllReduce
    params, example, _ = _fixture()
    with pytest.raises(ValueError, match="data-only"):
        serve.ServeEngine(_apply, params, example, (8,),
                          strategy_builder=ModelParallel(AllReduce(),
                                                         model_axis=2),
                          replicas=2)


def test_bucket_must_divide_data_axis():
    params, example, _ = _fixture()
    with pytest.raises(ValueError, match="not divisible"):
        serve.ServeEngine(_apply, params, example, (6,))  # 8 devices


def test_over_capacity_bucket_refused_at_engine_build(monkeypatch):
    """ISSUE 17 satellite: a bucket whose predicted peak exceeds the HBM
    capacity x headroom is refused BEFORE any AOT compile, with a named
    MemoryError-class failure pointing at the bucket and the dominant
    class — never a silent under-provisioned engine."""
    from autodist_tpu.observability.memory import InfeasibleMemoryError

    params, example, _ = _fixture()
    monkeypatch.setenv("AUTODIST_HBM_GB", "0.0001")  # ~100KiB toy device
    # The small bucket still fits under the toy capacity...
    serve.ServeEngine(_apply, params, example, (8,))
    # ...but a 4096-row bucket's activation live-set cannot.
    with pytest.raises(InfeasibleMemoryError, match="serve bucket 4096"):
        serve.ServeEngine(_apply, params, example, (8, 4096))
    assert issubclass(InfeasibleMemoryError, MemoryError)
    # The refusal names the dominant predicted class and the way out.
    with pytest.raises(InfeasibleMemoryError,
                       match="dominant class") as exc_info:
        serve.ServeEngine(_apply, params, example, (4096,))
    assert "AUTODIST_SERVE_BUCKETS" in str(exc_info.value)


# -- end-to-end acceptance ---------------------------------------------------


def test_serve_e2e_bitwise_with_report_and_latency_objective(tmp_path,
                                                             monkeypatch):
    """ISSUE 6 acceptance: a serve.Server on a models-zoo model answers N
    concurrent variable-sized requests equal, to rounding, to single-call
    apply_fn on the unpadded inputs (``_assert_matches_direct_apply`` says
    why not bitwise); p50/p99 latency and queue-depth
    gauges land in the report's Serving section; the serve_latency
    objective's ranking lands in the tuner sidecar."""
    import json
    import os
    from autodist_tpu import report, tuner

    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION",
                       str(tmp_path / "cal.json"))
    params, example, rng = _fixture()
    builder = tuner.AutoStrategy(
        objective="serve_latency",
        calibration=tuner.Calibration(path=str(tmp_path / "cal.json")))
    srv = serve.Server(_apply, params, example, buckets=(8, 32),
                       max_wait_ms=20, strategy_builder=builder)
    try:
        # serve_latency ranking persisted in the tuner sidecar.
        result = tuner.last_result()
        assert result is not None and result.objective == "serve_latency"
        sidecar = tuner.sidecar_path(result.chosen_strategy.id)
        assert os.path.exists(sidecar)
        with open(sidecar) as f:
            blob = json.load(f)
        assert blob["objective"] == "serve_latency"
        assert blob["ranking"][0]["rank"] == 1

        # N concurrent variable-sized requests from worker threads.
        inputs = [rng.randn(r, 16).astype(np.float32)
                  for r in (1, 3, 7, 8, 2, 5, 4, 6, 8, 1)]
        futs = [None] * len(inputs)

        def client(i):
            futs[i] = srv.submit(inputs[i])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(inputs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for x, f in zip(inputs, futs):
            _assert_matches_direct_apply(f.result(timeout=60), params, x)

        st = srv.stats()
        assert st["completed"] == len(inputs)
        snap = observability.registry().snapshot()
        lat = snap["histograms"]["serve.latency_ms"]
        assert lat["count"] == len(inputs)
        assert lat["p50"] is not None and lat["p99"] is not None
        assert lat["p99"] >= lat["p50"] > 0
        assert "serve.queue_depth" in snap["gauges"]

        path = report.render_report(srv.engine.program)
        with open(path) as f:
            html = f.read()
        assert "Serving" in html
        assert "p99" in html and "queue depth" in html
        assert "Replicas" in html and "utilization" in html
    finally:
        srv.close()


def test_closed_server_rejects_and_drains():
    params, example, rng = _fixture()
    srv = serve.Server(_apply, params, example, buckets=(8,), max_wait_ms=1)
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(rng.randn(2, 16).astype(np.float32))
    srv.close()  # idempotent


# -- (rows, seq) buckets for ragged prompts (ISSUE 19 satellite) -------------


def _lm_fixture(seed=0):
    from autodist_tpu.models import lm
    from autodist_tpu.models import transformer as T
    from autodist_tpu.models import layers as L

    cfg = lm.lm_tiny()
    params = lm.init(jax.random.PRNGKey(seed), cfg)

    def apply(p, tokens):
        return T.logits(p, cfg, T.encode(p, cfg, tokens,
                                         attn_fn=L.dot_product_attention))
    rng = np.random.RandomState(seed)
    example = rng.randint(0, cfg.vocab, (8, 16)).astype(np.int32)
    return cfg, params, apply, example, rng


def test_rows_seq_buckets_e2e_ragged_prompts():
    """A rank-2 bucketed Server pads BOTH the batch and the sequence dim
    of ragged token requests, routes to the fewest-padded-elements
    bucket, and de-pads each answer to exactly (rows, seq): bitwise
    equal to the reference forward on the same padded grid (zero
    row/column leakage from packing), and numerically equal to the
    unpadded forward (causal model: right-padding cannot reach earlier
    positions — only kernel-shape ulps differ)."""
    from autodist_tpu.serve.buckets import pick_bucket as pick

    cfg, params, apply, example, rng = _lm_fixture()
    buckets = ((8, 8), (8, 32))
    with serve.Server(apply, params, example, buckets=buckets,
                      max_wait_ms=1) as srv:
        ref = jax.jit(apply)
        for r, s in ((2, 5), (3, 8), (1, 20), (4, 3), (2, 17)):
            x = rng.randint(1, cfg.vocab, (r, s)).astype(np.int32)
            out = np.asarray(srv.infer(x, timeout=60))
            assert out.shape == (r, s, cfg.vocab)
            # Exact contract: the forward at this request's own bucket
            # grid, sliced back — padding must leak nothing.
            _, bseq = pick((r, s), list(buckets))
            padded = np.zeros((r, bseq), np.int32)
            padded[:, :s] = x
            np.testing.assert_array_equal(
                out, np.asarray(ref(params, padded))[:, :s])
            # Numeric contract vs the unpadded call (causality).
            np.testing.assert_allclose(out, np.asarray(ref(params, x)),
                                       rtol=2e-5, atol=2e-5)
        assert srv.last_dispatch["bucket"] in buckets


def test_rows_seq_submit_validation():
    cfg, params, apply, example, rng = _lm_fixture()
    with serve.Server(apply, params, example, buckets=((8, 16),),
                      max_wait_ms=1) as srv:
        with pytest.raises(ValueError, match="exceeds every bucket"):
            srv.submit(rng.randint(1, cfg.vocab, (2, 17)).astype(np.int32))
        with pytest.raises(ValueError, match="exceeds every bucket"):
            srv.submit(rng.randint(1, cfg.vocab, (9, 4)).astype(np.int32))
        out = srv.infer(rng.randint(1, cfg.vocab, (2, 7)).astype(np.int32),
                        timeout=60)
        assert out.shape == (2, 7, cfg.vocab)


# -- forced replica removal mid-flight (ISSUE 19 satellite) ------------------


def test_replica_removal_mid_flight_drops_nothing():
    """Forced removal of a replica with work still queued on it: the
    drained batches re-dispatch to the least-loaded survivors, every
    future completes with the right answer, and subsequent dispatch only
    ever consults the survivors."""
    params, example, rng = _fixture()
    with serve.Server(_apply, params, example, buckets=(4,),
                      max_wait_ms=1, replicas=2) as srv:
        victim = srv.engine.replicas[0]
        # Pile work straight onto the victim's queue, bypassing dispatch,
        # so removal MUST drain something.
        from autodist_tpu.serve.server import _Request
        stuffed = []
        for i in range(4):
            x = rng.randn(4, 16).astype(np.float32)
            req = _Request(1000 + i, x, 4)
            stuffed.append((x, req.future))
            victim.enqueue(x, [req], 4)
        removed_idx = victim.index
        n = srv.remove_replica(removed_idx)
        # Everything completes — re-dispatched or already in flight.
        for x, fut in stuffed:
            _assert_matches_direct_apply(fut.result(60), params, x)
        assert len(srv.engine.replicas) == 1
        assert srv.engine.replicas[0].index != removed_idx
        assert n >= 0
        # The survivor serves new traffic alone.
        x = rng.randn(3, 16).astype(np.float32)
        alone = np.asarray(srv.infer(x, timeout=60))
        _assert_matches_direct_apply(alone, params, x)
        # ... and answers the same rows again bitwise.
        np.testing.assert_array_equal(np.asarray(srv.infer(x, timeout=60)),
                                      alone)
        assert observability.registry().snapshot()[
            "gauges"]["serve.replicas"] == 1
        with pytest.raises(ValueError, match="last replica"):
            srv.remove_replica(srv.engine.replicas[0].index)


# -- measured serve latencies feed calibration (ISSUE 19 satellite) ----------


def test_serve_latencies_feed_calibration_and_report(tmp_path, monkeypatch):
    """Completions under the serve_latency objective close the
    predicted-vs-measured loop: record_measurement puts the error on the
    tuner result (report renders it), and a ``serve``-term calibration
    sample with ``serve:bucket*`` context lands in the sidecar."""
    from autodist_tpu import report, tuner
    from autodist_tpu.serve.server import Server

    cal_path = str(tmp_path / "cal.json")
    monkeypatch.setenv("AUTODIST_TUNER_CALIBRATION", cal_path)
    monkeypatch.setattr(Server, "_CAL_EVERY", 4)
    params, example, rng = _fixture()
    builder = tuner.AutoStrategy(
        objective="serve_latency",
        calibration=tuner.Calibration(path=cal_path))
    with serve.Server(_apply, params, example, buckets=(8,),
                      max_wait_ms=1, strategy_builder=builder) as srv:
        for _ in range(8):
            srv.infer(rng.randn(4, 16).astype(np.float32), timeout=60)
        result = tuner.last_result()
        assert result.measured_ms is not None
        assert result.prediction_error_pct is not None
        cal = tuner.Calibration.load(cal_path)
        samples = [s for s in cal.samples if s.get("term") == "serve"]
        assert samples, "no serve-term calibration observation recorded"
        assert samples[-1]["context"].startswith("serve:bucket")
        assert "serve" in cal.term_scales
        path = report.render_report(srv.engine.program)
        with open(path) as f:
            html = f.read()
        assert "prediction error" in html
