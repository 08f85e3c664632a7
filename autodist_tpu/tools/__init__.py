"""Offline operator tooling (no jax import required).

* :mod:`~autodist_tpu.tools.timeline` — merges every host's Chrome trace,
  flight log and skew summary under a working directory into one
  offset-corrected trace for Perfetto
  (``python -m autodist_tpu.tools.timeline <logdir>``).
"""
