"""Serving runtime: AOT-bucketed, continuously-batched inference.

The training stack — capture -> strategy/tuner -> AOT compile ->
remapper placement — generalized to the inference workload's inverted
constraints (docs/serving.md):

* :mod:`~autodist_tpu.serve.buckets` — public bucket selection
  (:func:`pick_bucket`): requests route to the smallest admissible
  padded bucket, compiled ahead of time;
* :mod:`~autodist_tpu.serve.engine` — the AOT bucket compiler and
  per-replica runtimes: params placed once and **never donated**,
  multi-replica mesh carving with least-loaded dispatch, depth-N
  prefetch overlap on the request path;
* :mod:`~autodist_tpu.serve.server` — the continuous-batching
  :class:`Server`: ``submit() -> Future``, coalescing under a max-wait
  deadline (``server.MAX_WAIT_MS`` where none is passed), FIFO packing, exact
  per-request de-padding;
* :mod:`~autodist_tpu.serve.decode` — the autoregressive
  :class:`DecodeServer`: slot-based KV-cache continuous batching
  (requests join/leave the in-flight batch every token) with zero-drop
  replica scaling;
* :mod:`~autodist_tpu.serve.autoscale` — the SLO-driven
  :class:`Autoscaler` watching ``serve.slo_burn`` + queue depth,
  escalating to ``Coordinator.grow``/``shrink`` at the fleet tier.

The tuner prices candidates for this workload under
``objective="serve_latency"`` (``AUTODIST_STRATEGY=auto`` picks it up
automatically inside the serve path).
"""
from autodist_tpu.serve.autoscale import Autoscaler, maybe_autoscaler  # noqa: F401
from autodist_tpu.serve.buckets import (buckets_from_env,  # noqa: F401
                                        normalize_buckets, pick_bucket)
from autodist_tpu.serve.decode import (DecodeEngine, DecodeServer,  # noqa: F401
                                       decode_buckets_from_env)
from autodist_tpu.serve.engine import (ReplicaRuntime, ServeEngine,  # noqa: F401
                                       build_replica_programs)
from autodist_tpu.serve.server import Server  # noqa: F401

__all__ = ["Server", "ServeEngine", "ReplicaRuntime", "DecodeServer",
           "DecodeEngine", "Autoscaler", "maybe_autoscaler",
           "build_replica_programs", "pick_bucket", "normalize_buckets",
           "buckets_from_env", "decode_buckets_from_env"]
