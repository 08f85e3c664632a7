"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain lists of
``(name, start_s, end_s)``; ``reduce`` turns them into what the per-layer
metrics read.  The interval arithmetic is separate and pure, so that it is
checked on hand-built intervals as well as on a recorded trace.

What a TPU trace looks like (jax 0.9.0 / libtpu 0.0.34, v5e; PERF.md has the
account): each chip is a plane ``/device:TPU:<n>``.  Its line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
whole text (``%fusion.12 = bf16[8,1024]{1,0:T(8,128)} fusion(...)``); a
Pallas kernel keeps its name (``%flash_fwd.7 = ...``).  ``XLA Modules`` holds
one event per executed program.  ``Async XLA Ops`` holds one event per
asynchronous instruction (``copy-start``, ``all-gather-start``, ...) for as
long as it is in flight, while its ``-start`` and ``-done`` halves are short
events on ``XLA Ops``.  A ``while`` or ``conditional`` instruction has an
event on ``XLA Ops`` too, which lasts from its first iteration's or its
branch's first event to the last one's end, beside those events: ``leaves``
takes such a container out before lengths are added up.  The host's
``TraceAnnotation`` spans are on the plane ``/host:CPU``, on the line of the
thread that made them, on the same clock.
"""
import collections
import functools
import gzip
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
CONTAINER = re.compile(r"^(while|conditional)$")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


# -- interval arithmetic (seconds; an interval is (start, end)) --------------

def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    merged = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [tuple(i) for i in merged]


def total(intervals):
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def intersect(a, b):
    """Intersection of two sorted, disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo, hi):
    """The parts of [lo, hi] that the sorted, disjoint ``busy`` leaves."""
    out, at = [], lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def leaves(events):
    """The ``(name, start, end)`` events of one line without its ``while``
    and ``conditional`` events that contain another event of it, in their
    order.  Such an event lasts as long as the events of its body together,
    so lengths added up with it count that time twice; the union of the
    line is the same with and without it but for what the loop costs
    between its body's events.  An event of any other kind stays, whatever
    lies inside its span (a fusion with a ``-start`` event in it is work),
    and an event of no length (the trace has some) is nobody's body."""
    order = sorted((i for i, e in enumerate(events) if e[2] > e[1]),
                   key=lambda i: (events[i][1], -events[i][2]))
    containers, open_ = set(), []
    for i in order:
        _, lo, hi = events[i]
        open_ = [j for j in open_ if events[j][2] > lo]
        containers.update(j for j in open_ if hi <= events[j][2])
        if CONTAINER.match(op_kind(events[i][0])):
            open_.append(i)
    return [e for i, e in enumerate(events) if i not in containers]


# -- reading -----------------------------------------------------------------

@functools.lru_cache(maxsize=1 << 17)
def op_name(event_name):
    """The HLO instruction's name: ``%fusion.12 = bf16[..] fusion(..)`` and
    ``fusion.12`` both give ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@functools.lru_cache(maxsize=1 << 17)
def op_kind(event_name):
    """The instruction's opcode where the event gives its text
    (``%x.5 = (s32[], f32[8]{0}) while(%t), body=...`` gives ``while``), and
    the group of its name where it gives the name alone."""
    name, _, rest = event_name.partition(" = ")
    called = re.search(r"\s([\w-]+)\(", re.sub(r"\{[^{}]*\}", "", rest))
    return called.group(1) if called else op_group(op_name(name))


def op_group(name):
    """``fusion.12`` -> ``fusion``: instructions of one kind and origin."""
    return re.sub(r"[.\d]+$", "", name)


@functools.lru_cache(maxsize=1 << 17)
def op_label(event_name, width=80):
    """What the breakdown calls an operation: its kind and its result's
    type without the layout, so that the same instruction of every layer
    adds up and the head's do not hide among them:
    ``%fusion.12 = bf16[8,1024]{1,0:T(8,128)} fusion(...)`` gives
    ``fusion bf16[8,1024]``."""
    name, _, rest = event_name.partition(" = ")
    result = re.sub(r"\{[^{}]*\}", "", rest)
    result = result[:result.find(")") + 1] if result.startswith("(") \
        else result.split(" ", 1)[0]
    return f"{op_group(op_name(name))} {result}".strip()[:width]


def load(path):
    """``{"chips": {n: {"ops": [...], "async": [...], "modules": [...]}},
    "host": [...]}`` with every event as ``(name, start_s, end_s)`` and the
    name as the trace gives it; host events are the benchmark's own
    annotations only."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    def events(line):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]

    trace = {"chips": {}, "host": []}
    for plane in data.planes:
        chip = DEVICE_PLANE.match(plane.name)
        if chip:
            lines = {line.name: events(line) for line in plane.lines
                     if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
            trace["chips"][int(chip.group(1))] = {
                "ops": lines.get(OPS_LINE, []),
                "async": lines.get(ASYNC_LINE, []),
                "modules": lines.get(MODULES_LINE, [])}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                trace["host"] += [e for e in events(line)
                                  if e[0].startswith("chipbench.")]
    return trace


# -- reducing ----------------------------------------------------------------

def collective_intervals(chip):
    """``[(name, start, end)]`` of a chip's collectives: a synchronous one
    is its event on the line of operations; an asynchronous one is in
    flight for its event on the line of asynchronous operations (its
    ``-start`` and ``-done`` halves on the line of operations lie inside
    that, and count as collective time too)."""
    return sorted(((op_name(name), lo, hi)
                   for name, lo, hi in chip["ops"] + chip.get("async", [])
                   if COLLECTIVE.match(op_name(name))), key=lambda e: e[1])


def _slice_of(chip, skip_programs):
    """The slice to reduce: from the start of program ``skip_programs`` to
    the end of the last one (the first programs after the profiler starts
    run on an empty queue); the whole span of the operations where the
    trace has no line of programs."""
    programs = sorted(chip["modules"], key=lambda e: e[1])
    if len(programs) > skip_programs:
        return programs[skip_programs][1], max(e[2] for e in programs), \
            len(programs) - skip_programs
    return (min(e[1] for e in chip["ops"]), max(e[2] for e in chip["ops"]),
            len(programs))


def reduce(trace, kernels=(), skip_programs=2, top=10, longest=5):
    """The numbers the per-layer metrics read, means over the chips.  Busy
    time is the union of every event; an operation's or a kernel's seconds
    are summed over ``leaves`` of the line."""
    chips = {n: c for n, c in trace["chips"].items() if c["ops"]}
    if not chips:
        raise ValueError("the trace holds no device plane with operations "
                         f"(planes matching {DEVICE_PLANE.pattern})")
    n = len(chips)
    out = {"chips": n, "window_s": 0.0, "busy_s": 0.0, "programs": 0,
           "collective_s": 0.0, "collective_exposed_s": 0.0}
    op_seconds = collections.Counter()
    kernel_seconds = collections.Counter()
    kernel_calls = collections.Counter()
    gap_owner = collections.Counter()
    longest_gaps = []
    in_flight = {}      # chip -> seconds of its collectives' union
    for number, chip in chips.items():
        lo, hi, programs = _slice_of(chip, skip_programs)
        inside = [(name, max(a, lo), min(b, hi)) for name, a, b in chip["ops"]
                  if min(b, hi) > max(a, lo)]
        coll_union = union(clip([(a, b) for _, a, b
                                 in collective_intervals(chip)], lo, hi))
        compute = union((a, b) for name, a, b in inside
                        if not COLLECTIVE.match(op_name(name)))
        busy = union((a, b) for _, a, b in inside)
        out["window_s"] += (hi - lo) / n
        out["busy_s"] += total(busy) / n
        out["programs"] += programs / n
        in_flight[number] = total(coll_union)
        out["collective_exposed_s"] += (
            total(coll_union) - total(intersect(coll_union, compute))) / n
        for name, a, b in leaves(inside):
            op_seconds[op_label(name)] += (b - a) / n
            for kernel in kernels:
                if kernel in op_name(name):
                    kernel_seconds[kernel] += (b - a) / n
                    kernel_calls[kernel] += 1 / n
                    break
        for a, b in gaps(busy, lo, hi):
            owner = _gap_owner(trace["host"], a, b)
            gap_owner[owner] += (b - a) / n
            longest_gaps.append((owner, b - a))
    # The v5e's profiler wrote the line of asynchronous operations for the
    # first chip only (PERF.md, PR 22); a chip without it shows the short
    # start and done halves alone.  Every chip runs the same program, so
    # where some chips have the line, the time in flight is theirs.
    seen = [x for c, x in in_flight.items() if chips[c].get("async")] \
        or list(in_flight.values())
    out["collective_s"] = sum(seen) / len(seen)
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    out["op_seconds"] = op_seconds.most_common(top)
    out["kernel_seconds"] = dict(kernel_seconds)
    out["kernel_calls"] = dict(kernel_calls)
    out["gap_seconds_by_owner"] = gap_owner.most_common(top)
    out["longest_gaps"] = sorted(longest_gaps, key=lambda g: -g[1])[:longest]
    return out


def _gap_owner(host, lo, hi):
    """The benchmark's annotation that covers most of [lo, hi]."""
    best, covered = "no annotation", 0.0
    for name, a, b in host:
        overlap = min(b, hi) - max(a, lo)
        if overlap > covered:
            best, covered = name, overlap
    return best
