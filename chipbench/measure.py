"""Clocks, host spans and the small arithmetic every driver shares."""
import contextlib
import math
import os
import time

import numpy as np


def process_age_s():
    """Seconds since this process was created (kernel's clock), so that
    set-up counts the interpreter's start and the imports."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Spans:
    """Host-clock spans around the benchmark's calls into each layer.

    ``span`` keeps (name, start, end) in memory; ``annotate`` only writes
    the name into the profiler's trace (for the hot loop, where the gaps of
    a traced slice are attributed to it).  Both use the same names.
    """

    PREFIX = "chipbench."

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.PREFIX + name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def annotate(self, name):
        import jax
        return jax.profiler.TraceAnnotation(self.PREFIX + name)

    def seconds(self, name):
        """Total seconds of the spans called ``name``; None if none ran."""
        found = [t1 - t0 for n, t0, t1 in self.records if n == name]
        return sum(found) if found else None


def percentile(values, q):
    """The ``q``-th percentile, linearly interpolated between samples."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def losses_ok(losses, rise_allowed=0.05, edge=10):
    """``(ok, detail)``: every loss finite, and the median of the last
    ``edge`` not above the median of the first ``edge`` by more than
    ``rise_allowed`` (uniform random tokens: the loss sinks slowly towards
    ln V and has no reason to rise)."""
    if not losses:
        return False, "no step completed in the window"
    bad = sum(not math.isfinite(x) for x in losses)
    if bad:
        return False, f"{bad} of {len(losses)} losses are not finite"
    first = float(np.median(losses[:edge]))
    last = float(np.median(losses[-edge:]))
    return (last <= first + rise_allowed,
            f"median loss {first:.4f} -> {last:.4f} over {len(losses)} steps")
