"""The gradients' bucket plan, the reader of a compiled step's communication
instructions, and the predicted exposed-comms figure.

Nothing here orders a step's communication: the compiler does (one chip,
small leaves, ``scan_layers`` models) and, for the explicit step's ``fsdp``
matrices, ``kernel/synchronization/grad_scatter`` behind ``layer_boundary``.
What this module holds is shared by the Runner (the explicit step's fused
reductions), the tuner's cost model (pricing) and the report (the predicted
figure; the text parser below also serves
``observability/profile.comm_table``, whose join with a trace is the measured
one):

* **Bucket plan** — gradient reductions are bucketed by strategy
  ``(group, compressor, dtype)`` and split at ``AUTODIST_AR_BUCKET_MB``;
  buckets are *issued in the order their last gradient is produced by the
  backward pass* (reverse-layer order), derived from the jaxpr's
  grad-production order.  The plan is a pure function of the captured
  program, so chief and workers derive the identical issue order with no
  coordination (the same contract as the tuner tie-break).

* **Exposed-comms model** — ``exposed_collective_ms`` walks a *scheduled*
  HLO text (instruction order == execution order), prices every async
  ``-start``/``-done`` pair on the topology's link seeds, and subtracts
  an HBM-roofline estimate of the compute scheduled inside each pair's
  window: what is left is communication the schedule could not hide —
  the gauge ``comms.exposed_ms_per_step``.
"""
import hashlib
import re
from collections import namedtuple

import jax

from autodist_tpu import const
from autodist_tpu.utils import logging


# -- grad-production order ---------------------------------------------------


def grad_production_order(graph_item):
    """{var_name: jaxpr equation index producing its gradient}.

    The backward pass materializes gradients in reverse layer order (the
    last layer's grad first); the producing equation's position in the
    ``jax.grad`` jaxpr is that order, and it is identical on every
    process tracing the same captured program — the determinism the
    bucket issue order rides on.  Returns ``{}`` when the program cannot
    be traced or the trace is opaque (e.g. one wrapping pjit): callers
    fall back to params flatten order, which is equally deterministic.
    """
    from jax.tree_util import tree_flatten_with_path, tree_map
    from autodist_tpu.graph_item import path_to_name
    if graph_item.loss_fn is None or graph_item.batch_struct is None:
        return {}
    try:
        params_struct = tree_map(
            lambda l: jax.ShapeDtypeStruct(jax.numpy.shape(l),
                                           jax.numpy.result_type(l)),
            graph_item.params)
        gfn = jax.grad(graph_item.loss_fn, has_aux=graph_item.aux_output)
        closed = jax.make_jaxpr(gfn)(params_struct, graph_item.batch_struct)
    except Exception as e:  # noqa: BLE001 - best-effort, order falls back
        logging.debug("grad production order unavailable: %s", e)
        return {}
    names = [path_to_name(p) for p, _ in
             tree_flatten_with_path(params_struct)[0]]
    produced_at = {}
    for i, eqn in enumerate(closed.jaxpr.eqns):
        for ov in eqn.outvars:
            produced_at[id(ov)] = i
    order = {}
    for nm, ov in zip(names, closed.jaxpr.outvars[:len(names)]):
        order[nm] = produced_at.get(id(ov), len(closed.jaxpr.eqns))
    if len(set(order.values())) <= 1 and len(order) > 1:
        return {}  # opaque trace (single wrapping eqn): no signal
    return order


# -- bucket plan -------------------------------------------------------------

#: One fused reduction: ``key`` is the strategy ``(group, compressor,
#: dtype)`` fusion key, ``names`` the member variables in grad-production
#: order, ``bytes`` the wire payload.
Bucket = namedtuple("Bucket", ["key", "names", "bytes"])


def bucket_bytes_cap(bucket_mb=None):
    """Effective fusion-bucket cap in bytes (0 => unbounded, the
    pre-knob behavior of one bucket per fusion key)."""
    if bucket_mb is None:
        bucket_mb = const.ENV.AUTODIST_AR_BUCKET_MB.val
    mb = max(0, int(bucket_mb))
    return mb * (1 << 20)


def bucket_plan(members, order=None, cap_bytes=0):
    """Deterministic fused-reduction plan.

    Args:
        members: ``[(name, fusion_key, nbytes)]`` — fusable variables with
            their strategy fusion key ``(group, compressor, dtype-str)``
            and wire payload bytes.
        order: ``{name: production_index}`` from
            :func:`grad_production_order` (missing names sort after known
            ones, by name).
        cap_bytes: split a fusion key's bucket when its payload would
            exceed this (0 = never split).

    Returns buckets sorted by *completion order* — the production index
    of each bucket's last gradient — so issuing them in list order
    matches "as gradients become available".  Ties break on the key/name,
    never on dict or hash order.
    """
    order = order or {}
    big = len(order) + len(members) + 1

    def rank(name):
        return (order.get(name, big), name)

    by_key = {}
    for name, key, nbytes in members:
        by_key.setdefault(tuple(key), []).append((name, float(nbytes)))
    buckets = []
    for key in sorted(by_key, key=str):
        entries = sorted(by_key[key], key=lambda e: rank(e[0]))
        cur_names, cur_bytes = [], 0.0
        for name, nbytes in entries:
            if cur_names and cap_bytes and cur_bytes + nbytes > cap_bytes:
                buckets.append(Bucket(key, tuple(cur_names), cur_bytes))
                cur_names, cur_bytes = [], 0.0
            cur_names.append(name)
            cur_bytes += nbytes
        if cur_names:
            buckets.append(Bucket(key, tuple(cur_names), cur_bytes))
    buckets.sort(key=lambda b: (rank(b.names[-1]), str(b.key)))
    return buckets


def plan_fingerprint(buckets):
    """Stable digest of a bucket plan (chief/worker agreement checks)."""
    text = ";".join(f"{b.key}:{','.join(b.names)}:{int(b.bytes)}"
                    for b in buckets)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# -- exposed-comms model over a scheduled HLO --------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
#: The collective opcodes; each also comes as a ``-start`` / ``-done`` pair.
_COLLECTIVES = ("all-reduce|all-gather|reduce-scatter|collective-permute|"
                "all-to-all")
_START_RE = re.compile(
    r"%?([\w.-]+)\s*=\s*(\([^=]*?\)|\S+)\s*"
    rf"((?:{_COLLECTIVES})-start)\(")
_DONE_RE = re.compile(rf"(?:{_COLLECTIVES})-done\(\s*%?([\w.-]+)")
#: An instruction's opcode where it is a collective: ``(kind, half)`` with
#: ``half`` one of ``-start``, ``-done`` or None.  Operands are names and
#: metadata spells its primitives with underscores, so a line has at most
#: one match.
_COLLECTIVE_RE = re.compile(rf"\s({_COLLECTIVES})(-start|-done)?\(")
_COMPUTE_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s*(?:fusion|dot|convolution|custom-call)\(")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _each_shape_bytes(text):
    """Byte-size of every shape token in ``text``, in order."""
    for m in _SHAPE_RE.finditer(text):
        n = 1
        dims = m.group(2)
        if dims:
            for d in dims.split(","):
                n *= int(d)
        yield n * _DTYPE_BYTES.get(m.group(1), 4)


def _shape_bytes(text):
    """Max tensor byte-size among the shape tokens in ``text`` (async
    starts return tuples holding operand and result aliases — the payload
    is the largest member)."""
    return max(_each_shape_bytes(text), default=0)


def _payload_bytes(kind, half, result, group):
    """Bytes of the whole array a collective reduces, gathers or moves, from
    its result type: a synchronous instruction's results all count (a
    combined all-reduce is a tuple of what it reduces; a reduce-scatter
    returns one shard of ``group``), a ``-start`` half's tuple holds
    operand and result aliases, so the largest member is the payload."""
    if half:
        return _shape_bytes(result)
    total = sum(_each_shape_bytes(result))
    return total * group if kind == "reduce-scatter" else total


def _group_size(line):
    m = _GROUP_IOTA_RE.search(line)
    if m:
        return max(1, int(m.group(2)))
    m = _GROUP_BRACE_RE.search(line)
    if m:
        return max(1, len([t for t in m.group(1).split(",") if t.strip()]))
    return 1


def async_collective_windows(hlo_text):
    """Parse a *scheduled* HLO text into async-collective windows.

    Returns ``[{op, name, bytes, group_size, window_compute_bytes,
    window_ops}]`` — one record per matched ``-start``/``-done`` pair,
    where the window fields describe the compute instructions the
    schedule placed between the pair (instruction order in a
    post-scheduling dump is execution order).  A window with zero compute
    means the collective is fully exposed: its ``-done`` was scheduled
    right behind its ``-start``.
    """
    open_pairs = {}  # start name -> record
    records = []
    for line in hlo_text.splitlines():
        m = _START_RE.search(line)
        if m:
            name, result, opstart = m.group(1), m.group(2), m.group(3)
            rec = {"op": opstart[:-len("-start")], "name": name,
                   "bytes": _shape_bytes(result) or _shape_bytes(line),
                   "group_size": _group_size(line),
                   "window_compute_bytes": 0.0, "window_ops": 0}
            open_pairs[name] = rec
            records.append(rec)
            continue
        m = _DONE_RE.search(line)
        if m:
            open_pairs.pop(m.group(1), None)
            continue
        if open_pairs:
            m = _COMPUTE_RE.search(line)
            if m:
                nbytes = _shape_bytes(m.group(1))
                for rec in open_pairs.values():
                    rec["window_compute_bytes"] += nbytes
                    rec["window_ops"] += 1
    return records


def exposed_collective_ms(hlo_text, topology=None, unroll=1):
    """``comms_exposed_ms_per_step`` from a scheduled HLO text.

    Every async pair is priced on ``topology`` (collective cost from the
    payload bytes + replica-group size); the compute inside its window is
    priced at the HBM roofline (bytes moved / HBM bandwidth — a
    deliberate *underestimate* of hiding, so the metric errs toward
    reporting comms as exposed).  Synchronous collectives (no async form
    in the schedule) are fully exposed by definition and counted whole.
    ``unroll`` divides the total for megastep programs (K steps per
    dispatch).
    """
    from autodist_tpu.tuner.cost_model import Topology
    if topology is None:
        topology = Topology(max(1, len(jax.devices())),
                            max(1, jax.process_count()))
    total = 0.0
    for rec in async_collective_windows(hlo_text):
        comm_s = _priced_collective_s(topology, rec["op"], rec["bytes"],
                                      rec["group_size"])
        hidden_s = rec["window_compute_bytes"] / topology.hbm_bytes_per_s
        total += max(0.0, comm_s - hidden_s)
    total += _sync_collective_s(hlo_text, topology)
    return total * 1e3 / max(1, int(unroll))


def _priced_collective_s(topology, op, nbytes, group_size):
    if op == "all-reduce":
        return topology.all_reduce_cost(nbytes, group_size)
    if op == "reduce-scatter":
        return topology.reduce_scatter_cost(nbytes, group_size)
    if op == "all-gather":
        # The payload shape in the start line is the gathered result; the
        # per-device contribution rides one ring sweep of it.
        return topology.all_gather_cost(nbytes, group_size)
    return topology.p2p_cost(nbytes, cross_host=group_size >
                             topology.devices_per_host)


_SYNC_RE = re.compile(
    r"%?[\w.-]+\s*=\s*(\([^=]*?\)|\S+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all)(?:\.\d+)?\(")


def _sync_collective_s(hlo_text, topology):
    """Non-async collectives in the schedule: nothing can hide them."""
    total = 0.0
    for line in hlo_text.splitlines():
        m = _SYNC_RE.search(line)
        if m is None or "-start" in line or "-done" in line:
            continue
        total += _priced_collective_s(topology, m.group(2),
                                      _shape_bytes(m.group(1)),
                                      _group_size(line))
    return total
