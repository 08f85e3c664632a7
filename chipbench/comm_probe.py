"""The program's account of a step's communication, for the six readers of
the Collectives layer that take it from the program.

``autodist_tpu.observability.profile.comm_table`` says which instructions of
the compiled step are communication (the compiler's fused forms among them,
which the trace names ``fusion.N``), what each carries and where the
program's scope table places it; ``profile.comm_time`` joins that with one
chip's events.  Here the traced slice is loaded with its line of
asynchronous operations and the step's text is taken, once a process each.

Where the program has no ``comm_table`` (a commit from before it existed),
where the step holds no communication instruction (one chip), or where the
run left no trace, ``measured`` returns None and none of the readers raises.
"""
import functools
import json
import os
import time

from chipbench import program_probe, trace_reduce


def _say(text):
    print(f"chipbench: comm_probe: {text}", flush=True)


def measured(run):
    """Of this process's traced slice, means over the chips: ``window_s``,
    ``steps`` (programs in the slice), ``comm_s`` and ``exposed_s`` with
    ``by_kind`` and ``by_scope`` (``profile.comm_time``), and of the step's
    text ``wire_bytes`` a chip sends a step by kind and
    ``wire_bytes_by_scope``; None where there is nothing to read."""
    path = program_probe.trace_path() if run["trace"] is not None else None
    return _measured(path, os.path.getmtime(path)) if path else None


@functools.lru_cache(maxsize=1)
def _measured(path, _mtime):
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import profile
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    if not hasattr(profile, "comm_table") or not hasattr(runner,
                                                         "step_text"):
        return None
    t0 = time.perf_counter()
    table = profile.comm_table(runner.step_text())
    _say(f"comm_table() of the step took {time.perf_counter() - t0:.3f} s "
         f"for {len(table)} communication instructions")
    if not table:
        return None
    t0 = time.perf_counter()
    out = reduce(trace_reduce.load(path), table, profile.comm_time)
    out["wire_bytes"] = profile.comm_wire_bytes(table)
    out["wire_bytes_by_scope"] = profile.comm_wire_bytes(table, by="scope")
    _say(f"loading the trace and comm_time() over {out['chips']} chips took "
         f"{time.perf_counter() - t0:.3f} s")
    window = out["window_s"]
    for key in ("by_kind", "by_scope"):
        _say(f"communication in flight {key.replace('_', ' ')}, % of the "
             f"slice: " + json.dumps({k: round(100.0 * v / window, 3)
                                      for k, v in sorted(out[key].items())}))
    _say("bytes a chip sends a step by kind, GB: " + json.dumps(
        {k: round(v / 1e9, 4) for k, v in sorted(out["wire_bytes"].items())})
        + "; by scope: " + json.dumps(
            {k: round(v / 1e9, 4) for k, v
             in sorted(out["wire_bytes_by_scope"].items())}))
    return out


def reduce(trace, table, comm_time, skip_programs=2):
    """``comm_time`` of each chip's part of the slice ``trace_reduce.reduce``
    takes (``trace`` is ``trace_reduce.load``'s), means over the chips."""
    chips = {n: c for n, c in trace["chips"].items() if c["ops"]}
    out = {"chips": len(chips), "window_s": 0.0, "steps": 0.0,
           "comm_s": 0.0, "exposed_s": 0.0, "by_kind": {}, "by_scope": {}}
    for chip in chips.values():
        lo, hi, programs = trace_reduce._slice_of(chip, skip_programs)

        def inside(events):
            return [(trace_reduce.op_name(name), max(a, lo), min(b, hi))
                    for name, a, b in events if min(b, hi) > max(a, lo)]

        found = comm_time(inside(chip["ops"]), inside(chip.get("async", [])),
                          table)
        out["window_s"] += (hi - lo) / len(chips)
        out["steps"] += programs / len(chips)
        for key in ("comm_s", "exposed_s"):
            out[key] += found[key] / len(chips)
        for key in ("by_kind", "by_scope"):
            for name, seconds in found[key].items():
                out[key][name] = out[key].get(name, 0.0) \
                    + seconds / len(chips)
    return out


def share(run, key, name=None):
    """``measured``'s ``key`` (of it ``name``, where it is a table) over the
    traced slice, in percent; None with nothing to read."""
    found = measured(run)
    if not found or not found["window_s"]:
        return None
    seconds = found[key] if name is None else found[key].get(name, 0.0)
    return 100.0 * seconds / found["window_s"]


def scope_share(run, scope):
    """Busy time of the instructions the program's scope table places in
    ``scope`` over the slice's busy time (``program_probe.share``), where
    the program places communication by what it is; None elsewhere."""
    if measured(run) is None:
        return None
    return program_probe.share(run, "scope", scope)
