"""The program's own account of a run, for the per-layer readers.

Three things are found here and nowhere else: the program's spans
(``autodist_tpu.observability.tracing``, put on the ``perf_counter`` axis
the benchmark's own spans use), the program's runner
(``get_default_autodist().runner``, for the table from instruction to named
scope), and the traced run's ``.xplane.pb`` under
``<root>/.chipbench_work/trace/``, which is loaded and joined with that table
once a process.

Where the program has no such span, counter or table (a commit from before
they existed), every function here returns None and none raises: the
result line then leaves the metric out.
"""
import collections
import functools
import glob
import gzip
import json
import os
import time

from chipbench import measure, trace_reduce
from chipbench.catalog import ROOT

ANNOTATION_PREFIXES = ("chipbench.", "autodist.")
UNATTRIBUTED = "(unattributed)"
TABLE_FILE = "scope_table.json.gz"


def _say(text):
    print(f"chipbench: program_probe: {text}", flush=True)


def on_chip():
    """Seconds of set-up and counts of compiles are numbers of the machine
    with the chip; elsewhere (the tests' toy cells, on the CPU) the readers
    report nothing, as a reader with nothing to read does."""
    import jax
    return jax.devices()[0].platform == "tpu"


# -- the program's spans -----------------------------------------------------

def program_spans():
    """``[(name, start, end, args)]`` of the program's completed spans, in
    ``perf_counter`` seconds; None where the program cannot place them."""
    try:
        from autodist_tpu.observability import tracing
    except ImportError:
        return None
    to_perf = getattr(tracing, "to_perf_counter", None)
    if to_perf is None or not on_chip():
        return None
    return [(e["name"], to_perf(e["ts"]), to_perf(e["ts"] + e["dur"]),
             e.get("args", {}))
            for e in tracing.events() if e.get("ph") == "X"]


def window(run):
    """``(opened, closed)``: the window opens where the benchmark's
    ``warmup`` span ends; None if the run has no such span."""
    ends = [t1 for name, _, t1 in run["spans"].records if name == "warmup"]
    return (ends[-1], ends[-1] + run["window_s"]) if ends else None


def inside(spans, lo, hi, name=None):
    return [s for s in spans if lo <= s[1] and s[2] <= hi
            and (name is None or s[0] == name)]


def outermost(spans):
    """JAX traces and lowers inner functions inside outer ones: the spans
    that lie in no other span of their name."""
    return [s for s in spans if not any(
        o is not s and o[0] == s[0] and o[1] <= s[1] and s[2] <= o[2]
        for o in spans)]


def _seconds(spans):
    return sum(t1 - t0 for _, t0, t1, _ in spans)


def setup_split(run):
    """What the cell's session spent before the window opened, by the
    program's spans: ``trace_lower_s`` and ``xla_compile_s`` inside the
    ``compile`` span (the last one before the window), ``report_s``,
    ``create_state_s``.  The session begins at the program's last
    ``capture`` span before the window.  None where the spans are not
    there."""
    spans, opened = program_spans(), window(run)
    if not spans or opened is None:
        return None
    return _setup_split(opened[0])


@functools.lru_cache(maxsize=4)
def _setup_split(opened):
    spans = program_spans()
    captures = inside(spans, 0.0, opened, "capture")
    session = inside(spans, captures[-1][1] if captures else 0.0, opened)
    compiles = [s for s in session if s[0] == "compile"]
    states = [s for s in session if s[0] == "create-state"]
    if not compiles or not states or not inside(
            session, compiles[-1][1], compiles[-1][2], "xla-compile"):
        return None
    _, lo, hi, _ = compiles[-1]
    children = {name: outermost(inside(session, lo, hi, name))
                for name in ("jax-trace", "jax-lower", "xla-compile")}
    out = {"compile_span_s": hi - lo,
           "trace_lower_s": _seconds(children["jax-trace"]
                                     + children["jax-lower"]),
           "xla_compile_s": _seconds(children["xla-compile"]),
           "report_s": _seconds([s for s in session if s[0] == "report"]),
           "create_state_s": _seconds(states)}
    parts = {name: round(_seconds(found), 3) for name, found in children.items()}
    parts.update({name: round(_seconds([s for s in session if s[0] == name]),
                              3) for name in ("build-step", "init",
                                              "host-copy")})
    _say("set-up of the cell's session by the program's spans (s) "
         + json.dumps({k: round(v, 3) for k, v in out.items()})
         + ", parts " + json.dumps(parts))
    return out


def compiles_in_window(run):
    """``xla-compile`` spans (backend compiles or cache reads, of any jit in
    the process) that start after the window opened and before it closed."""
    spans, opened = program_spans(), window(run)
    compiles = [s for s in spans or () if s[0] == "xla-compile"]
    if opened is None or not compiles:
        return None
    found = [s for s in compiles if opened[0] < s[1] < opened[1]]
    for _, t0, t1, args in found:
        _say(f"compiled inside the window, {t0 - opened[0]:.3f} s in, for "
             f"{t1 - t0:.3f} s: {args.get('fun_name')}")
    return len(found)


# -- the device trace, by named scope ----------------------------------------

def trace_path(root=ROOT):
    """The ``.xplane.pb`` this process's traced slice left; None if there
    is none, or none written since this process began."""
    born = time.time() - measure.process_age_s()
    paths = [p for p in glob.glob(os.path.join(
        root, ".chipbench_work", "trace", "plugins", "profile", "*",
        "*.xplane.pb")) if os.path.getmtime(p) >= born]
    return paths[0] if len(paths) == 1 else None


def load(path):
    """``trace_reduce.load``'s shape with the operations named by their
    instruction alone and with both kinds of host annotation: the
    benchmark's (``chipbench.*``) and the program's (``autodist.*``)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))

    def events(line, rename=str):
        return [(rename(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]

    trace = {"chips": {}, "host": []}
    for plane in data.planes:
        chip = trace_reduce.DEVICE_PLANE.match(plane.name)
        if chip:
            lines = {line.name: line for line in plane.lines}
            trace["chips"][int(chip.group(1))] = {
                "ops": events(lines[trace_reduce.OPS_LINE],
                              trace_reduce.op_name)
                if trace_reduce.OPS_LINE in lines else [],
                "modules": events(lines[trace_reduce.MODULES_LINE])
                if trace_reduce.MODULES_LINE in lines else []}
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                trace["host"] += [e for e in events(line)
                                  if e[0].startswith(ANNOTATION_PREFIXES)]
    return trace


def join(trace, table, by_scope, mixed=(), skip_programs=2):
    """Seconds of the traced slice by scope and by phase, the chips' busy
    seconds, the seconds no scope claims by kind of instruction
    (``unplaced``), the seconds in fusions that hold more than one scope by
    the scopes they hold (``mixed``: the program's ``mixed_fusions``), and
    the slice's idle gaps by the ``autodist.*`` annotation that covers most
    of each; means over the chips.  ``by_scope`` is the program's
    ``device_time_by_scope``; the slice is the one ``trace_reduce.reduce``
    takes, and like it this adds up ``trace_reduce.leaves`` of a chip's
    line: a ``while`` event spans its body's events and is none of them
    (``inside_containers_s``: what of the busy time only such an event
    covers, the loops' cost between their bodies' operations)."""
    chips = {n: c for n, c in trace["chips"].items() if c["ops"]}
    own = [e for e in trace["host"] if e[0].startswith("autodist.")]
    out = {"scope": collections.Counter(), "phase": collections.Counter(),
           "gaps": collections.Counter(), "unplaced": collections.Counter(),
           "mixed": collections.Counter(), "busy_s": 0.0,
           "inside_containers_s": 0.0, "chips": len(chips)}
    for chip in chips.values():
        lo, hi, _ = trace_reduce._slice_of(chip, skip_programs)
        ops = [(name, max(a, lo), min(b, hi)) for name, a, b in chip["ops"]
               if min(b, hi) > max(a, lo)]
        busy = trace_reduce.union((a, b) for _, a, b in ops)
        out["busy_s"] += trace_reduce.total(busy) / len(chips)
        ops = trace_reduce.leaves(ops)
        out["inside_containers_s"] += (
            trace_reduce.total(busy) - trace_reduce.total(trace_reduce.union(
                (a, b) for _, a, b in ops))) / len(chips)
        seconds = by_scope(ops, table)
        for kind in ("scope", "phase"):
            for key, value in seconds[kind].items():
                out[kind][key] += value / len(chips)
        for name, a, b in ops:
            if table.get(name, (UNATTRIBUTED,))[0] == UNATTRIBUTED:
                out["unplaced"][trace_reduce.op_group(name)] += \
                    (b - a) / len(chips)
            if name in mixed:
                out["mixed"]["+".join(sorted(mixed[name]))] += \
                    (b - a) / len(chips)
        for a, b in trace_reduce.gaps(busy, lo, hi):
            out["gaps"][trace_reduce._gap_owner(own, a, b)] += \
                (b - a) / len(chips)
    return out


def by_scope():
    """``join`` of this process's traced slice with the table of the
    program's step; None where either is missing."""
    path = trace_path()
    return _by_scope(path, os.path.getmtime(path)) if path else None


@functools.lru_cache(maxsize=1)
def _by_scope(path, _mtime):
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import metrics, profile
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    join_fn = getattr(profile, "device_time_by_scope", None)
    if join_fn is None or not hasattr(runner, "step_text"):
        return None

    def counters():
        found = metrics.registry().snapshot()["counters"]
        return {name: found.get(name, 0) for name in (
            "compile.count", "compile.cache_hits", "compile.cache_misses")}

    # Runner.scope_table() is profile.scope_table(step_text()): taken apart
    # here so that the text is lowered and printed once for both readings.
    before, t0 = counters(), time.perf_counter()
    text = runner.step_text()
    table = profile.scope_table(text)
    _say(f"scope_table() of the step took {time.perf_counter() - t0:.3f} s "
         f"for {len(table)} instructions; counters before "
         f"{json.dumps(before)}, after {json.dumps(counters())}")
    with gzip.open(os.path.join(os.path.dirname(path), TABLE_FILE),
                   "wt") as f:
        json.dump(table, f)
    mixed = profile.mixed_fusions(text)
    trace = load(path)
    _say("host annotations in the trace: " + json.dumps(
        collections.Counter(name for name, _, _ in trace["host"])))
    joined = join(trace, table, join_fn, mixed)
    busy = joined["busy_s"]
    for kind in ("scope", "phase"):
        _say(f"busy time by {kind}, % of {busy * 1e3:.3f} ms a chip: "
             + json.dumps({k: round(100.0 * v / busy, 3) for k, v
                           in joined[kind].most_common()}))
    _say("busy time in fusions that hold more than one scope (placed by "
         "the vote of their instructions), %: "
         + json.dumps({k: round(100.0 * v / busy, 3) for k, v
                       in joined["mixed"].most_common(6)}))
    _say("busy time no scope claims, by kind of instruction, %: "
         + json.dumps({k: round(100.0 * v / busy, 3) for k, v
                       in joined["unplaced"].most_common(6)}))
    _say("busy time that only a while or conditional event covers (left "
         "out of every share), %: "
         f"{100.0 * joined['inside_containers_s'] / busy:.3f}")
    _say("idle gaps of the slice by the program's annotation, us: "
         + json.dumps({k: round(v * 1e6, 1) for k, v
                       in joined["gaps"].most_common(5)}))
    return joined


def share(run, kind, key):
    """Busy time of scope or phase ``key`` over busy time of the slice, in
    percent; None without a traced slice or without the program's table."""
    joined = by_scope() if run["trace"] is not None else None
    if not joined or not joined["busy_s"]:
        return None
    return 100.0 * joined[kind].get(key, 0.0) / joined["busy_s"]
