"""autodist_tpu: a TPU-native distributed training engine.

Users write single-device JAX training code; the framework compiles a
per-parameter distribution strategy (replication, AllReduce, sharded
PS-style state, partitioning, load balancing, hybrid dense/sparse sync,
gradient compression, bounded staleness) from the captured program plus a
cluster/pod resource spec, and executes it as one SPMD program over the
ICI/DCN mesh.

Capability parity with ``petuum/autodist`` (see SURVEY.md); architecture is
JAX/XLA-first: strategies lower to ``jax.sharding`` annotations (GSPMD) or a
``shard_map`` explicit-collective path — no graph surgery, no SSH fabric.
"""
from autodist_tpu._version import __version__
from autodist_tpu.autodist import AutoDist, get_default_autodist

__all__ = ["AutoDist", "get_default_autodist", "__version__"]
