"""Per-layer device-time profiler: scope provenance end to end.

PR 8's attribution ledger reconciles a step into ``device_compute`` /
``exposed_comms`` / ... — but those terms are opaque blobs: a regression
in one attention block reads as "compute got slower".  This module
splits the two device-side terms *per model scope*, threading provenance
through three layers:

* **model code** — the zoo's forward blocks run under ``jax.named_scope``
  (``"layer0/attn"``, ``"stage1/block2"``, ...), so every traced
  equation carries a scope on its name stack;
* **jaxpr** — :meth:`GraphItem.op_provenance` records eqn -> scope ->
  flops/bytes (the same per-eqn FLOP rules ``flops_estimate`` sums), and
  strategy variables join by name prefix (``"layer0/attn/query/kernel"``
  belongs to ``layer0/attn``) — per-scope *predicted* compute, comms,
  and wire bytes;
* **HLO** — the scheduled HLO's ``op_name`` metadata preserves the same
  scope paths through ``jvp``/``transpose`` wrappers and fusion; when
  the AOT path recorded that text, per-scope *measured structure* comes
  from the actual instruction stream (compute ops at the HBM roofline,
  collectives priced on the topology — reusing ``kernel/overlap``'s
  parsers).

Reconciliation closes the loop against the step ledger
(``observability/attribution.py``): per-scope shares are normalized so
per-scope compute sums exactly to the ledger's ``device_compute`` and
per-scope comms to ``exposed_comms`` — anything no scope claims stays in
an explicit ``(unattributed)`` bucket, **surfaced, never absorbed**
(the same residual discipline as the ledger itself).  Per-scope
measured-vs-predicted deltas feed :meth:`Calibration.observe_term` as
per-class observations — the per-op cost data ROADMAP item 3's sharding
searcher starts from.

Cost discipline: everything here runs ONCE per ``Runner.run``, on the
cold finalize path (``AUTODIST_PROFILE``, default on); with
``AUTODIST_TELEMETRY=0`` the step loop makes provably zero profiling
calls (spy-pinned).
"""
import json
import os
import re

from autodist_tpu import const
from autodist_tpu.utils import logging

#: The explicit remainder bucket — never folded into a named scope.
#: Shared with the provenance layer (graph_item) and the automap walker
#: so "unattributed" is one spelling everywhere.
from autodist_tpu.graph_item import UNATTRIBUTED  # noqa: E402,F401

#: Scope aggregation depth: "layer0/attn/bhqd,bhkd->bhqk" (einsum
#: sub-scopes) collapses into "layer0/attn"; the zoo's own scopes are at
#: most two segments deep ("stage0/block1").
SCOPE_DEPTH = 2
#: Scopes surfaced on the monitor, the gauges and the report.
TOPK = 5

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')

_last_profile = None


def enabled():
    """Profiler gate: telemetry master switch AND ``AUTODIST_PROFILE``."""
    from autodist_tpu import observability
    return observability.enabled() and bool(const.ENV.AUTODIST_PROFILE.val)


def collapse(scope, depth=SCOPE_DEPTH):
    """Cap a scope path at ``depth`` segments (sub-scopes aggregate up)."""
    if not scope:
        return ""
    return "/".join(scope.split("/")[:depth])


def scope_of(path_text, known_scopes):
    """Attribute a name-stack / HLO ``op_name`` / variable name to the
    longest known scope that prefixes it segment-wise, or ``None``.

    ``"jit(f)/transpose(jvp(layer0))/attn/dot_general"`` matches scope
    ``"layer0/attn"``; ``"layer0/attn/query/kernel"`` (a variable name)
    matches the same row — compute and comms land on one key.  So does
    a looped model's stack: ``pass/layer0/attn/...`` is ``layer0/attn``,
    the variables' one row.
    """
    from autodist_tpu.graph_item import scope_path, strip_pass
    segs = [s for s in strip_pass(scope_path(path_text)).split("/") if s]
    for i in range(min(len(segs), SCOPE_DEPTH + 1), 0, -1):
        cand = "/".join(segs[:i])
        if cand in known_scopes:
            return cand
    return None


def _zero():
    return {"compute_ms": 0.0, "comms_ms": 0.0, "wire_bytes": 0.0, "ops": 0}


# ---------------------------------------------------------------------------
# model-side (jaxpr + strategy) per-scope costs — always available


def model_scope_costs(runner, unroll=1):
    """Per-scope *predicted* costs from the captured program:

    * compute: per-scope forward FLOPs (3x fwd+bwd, spread over devices)
      from the jaxpr provenance, plus the optimizer-HBM update term
      attributed to the variable's owning scope;
    * comms: per-variable collective cost (compressor-aware wire bytes)
      priced on the topology, attributed by variable-name prefix.

    Returns ``(scopes, known)`` where ``scopes`` maps scope (or
    :data:`UNATTRIBUTED`) to cost records and ``known`` is the named
    scope set HLO/variable attribution matches against.
    """
    import jax
    from autodist_tpu.tuner import cost_model as cm
    prog = runner.program
    item = prog.graph_item
    topo = cm.Topology(max(1, prog.mesh.devices.size),
                       num_hosts=max(1, jax.process_count()))
    scopes, known = {}, set()
    from autodist_tpu.graph_item import strip_pass
    for scope, agg in item.scope_costs().items():
        key = collapse(strip_pass(scope)) or UNATTRIBUTED
        if key != UNATTRIBUTED:
            known.add(key)
        rec = scopes.setdefault(key, _zero())
        rec["compute_ms"] += 3.0 * agg["flops"] / \
            (topo.num_devices * topo.device_flops) * 1e3
        rec["ops"] += agg["ops"]

    # Per-variable update + sync terms (the cost model's own splitter —
    # fused AR groups are priced per variable here, which over-counts
    # bucket latency slightly but keeps attribution per-layer).
    model = cm.CostModel(topo)
    axes = dict(prog.strategy.graph_config.mesh_axes) or \
        {const.MESH_AXIS_DATA: topo.num_devices}
    n_data = max(1, axes.get(const.MESH_AXIS_DATA, topo.num_devices))
    for var in item.trainable_variables:
        node = prog.strategy.node_by_name(var.name)
        deferred = {}
        rs, ag, oth, elems, wire = model._var_sync_cost(
            var, node, n_data, deferred)
        comms_s = rs + ag + oth
        hosts = topo._hosts_spanned(n_data)
        for wire_b, raw_b, codec, sparse_b in deferred.values():
            if codec and hosts > 1:
                comms_s += topo.hierarchical_ar_cost(
                    raw_b, n_data, cm.hier_dcn_factor(codec, hosts))
                flat_b = sparse_b  # sparse rides its own flat ring
            else:
                flat_b = wire_b + sparse_b
            if flat_b:
                comms_s += topo.all_reduce_cost(flat_b, n_data)
        key = scope_of(var.name, known) or UNATTRIBUTED
        rec = scopes.setdefault(key, _zero())
        rec["comms_ms"] += comms_s * 1e3
        rec["wire_bytes"] += wire
        rec["compute_ms"] += elems * cm.UPDATE_BYTES_PER_ELEM / \
            topo.hbm_bytes_per_s * 1e3
    return scopes, known


# ---------------------------------------------------------------------------
# HLO-side per-scope costs — when the scheduled text was recorded


def hlo_scope_costs(hlo_text, known_scopes, topology=None, unroll=1):
    """Per-scope costs from a *scheduled* HLO text's op metadata.

    Reuses ``kernel/overlap``'s line parsers: compute instructions
    (fusion/dot/convolution/custom-call) are priced at the HBM roofline
    on their result bytes, collectives (async ``-start`` and sync forms)
    at the topology's collective cost with their payload as wire bytes.
    Each instruction lands on the longest known scope its ``op_name``
    carries; scope-less instructions land on :data:`UNATTRIBUTED` —
    the honest "the compiler emitted work no model scope claims" bucket.
    """
    import jax
    from autodist_tpu.kernel import overlap as ov
    from autodist_tpu.tuner.cost_model import Topology
    if topology is None:
        topology = Topology(max(1, len(jax.devices())),
                            max(1, jax.process_count()))
    unroll = max(1, int(unroll))
    scopes = {}

    def rec_for(line):
        m = _OP_NAME_RE.search(line)
        key = (scope_of(m.group(1), known_scopes) if m else None) \
            or UNATTRIBUTED
        return scopes.setdefault(key, _zero())

    for line in hlo_text.splitlines():
        m = ov._START_RE.search(line)
        if m is None:
            m_sync = ov._SYNC_RE.search(line)
            if m_sync is not None and "-done" not in line:
                nbytes = ov._shape_bytes(m_sync.group(1))
                rec = rec_for(line)
                rec["comms_ms"] += ov._priced_collective_s(
                    topology, m_sync.group(2), nbytes,
                    ov._group_size(line)) * 1e3 / unroll
                rec["wire_bytes"] += nbytes / unroll
                rec["ops"] += 1
                continue
            m_comp = ov._COMPUTE_RE.search(line)
            if m_comp is not None:
                rec = rec_for(line)
                rec["compute_ms"] += ov._shape_bytes(m_comp.group(1)) / \
                    topology.hbm_bytes_per_s * 1e3 / unroll
                rec["ops"] += 1
            continue
        nbytes = ov._shape_bytes(m.group(2)) or ov._shape_bytes(line)
        rec = rec_for(line)
        rec["comms_ms"] += ov._priced_collective_s(
            topology, m.group(3)[:-len("-start")], nbytes,
            ov._group_size(line)) * 1e3 / unroll
        rec["wire_bytes"] += nbytes / unroll
        rec["ops"] += 1
    return scopes


# ---------------------------------------------------------------------------
# measured per-scope device time: compiled text x device trace


#: The parts of a train step the Runner names (``runner.py``); their work
#: is neither forward nor backward.  ``loss_sync`` is the explicit step's
#: mean of loss and ``aux`` over the data axis: a reduction that is no
#: gradient's.
UPDATE_SCOPES = ("optimizer", "grad_sync", "param_gather", "loss_sync")
#: Where a communication instruction goes that carries no named scope (the
#: compiler's rewrites drop the ``op_name``): by what it does.
COMM_SCOPE_OF_KIND = {"all-reduce": "grad_sync", "reduce-scatter": "grad_sync",
                      "all-gather": "param_gather"}
#: Model scopes that fold into one row: the output projection and the loss.
HEAD_SCOPES = ("logits", "lm_head", "mlm_head")
#: Block sub-scopes that fold over every layer (``layer<i>/attn`` -> ``attn``).
BLOCK_SCOPES = ("attn", "mlp")
#: The expert layer folds over the layers one level further down, by its own
#: sub-scopes: ``layer<i>/moe/router`` -> ``moe/router`` (likewise
#: ``moe/dispatch`` and ``moe/experts``; what sits in none of them is ``moe``).
MOE_SCOPE = "moe"
#: So does the gated-delta mixer: ``layer<i>/gdn/scan`` -> ``gdn/scan``
#: (likewise ``gdn/proj``, ``gdn/conv``, ``gdn/gates``, ``gdn/out``; the
#: norm of the mixer's output, in none of them, is ``gdn``).
GDN_SCOPE = "gdn"
#: And the mixer with a decay a channel: ``layer<i>/kda/scan`` -> ``kda/scan``
#: (``kda/proj``, ``kda/conv``, ``kda/gates``, ``kda/out``).
KDA_SCOPE = "kda"
#: The router's group-limited choice keeps a level more:
#: ``layer<i>/moe/router/groups`` -> ``moe/router/groups``, a row beside
#: ``moe/router``.
ROUTER_GROUPS = ("moe", "router", "groups")
#: The multi-token-prediction module runs under the top-level scope ``mtp``
#: and its block's, its head's and its loss's scopes fold into the generic
#: rows with the model's own (``mtp/block/attn`` -> ``attn``,
#: ``mtp/block/moe/router`` -> ``moe/router``, ``mtp/lm_head`` -> ``head``);
#: what is the module's alone keeps its path (``mtp/proj``).
#: :func:`overlay_table` gives the module's total beside those rows.
MTP_SCOPE = "mtp"

_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")


def _user_scopes(op_name):
    """The user's scopes one HLO ``op_name`` was traced under (the name
    stack's last segment is the primitive), less the leading ``pass`` or
    ``pass<t>`` of a looped model; what the scan over the passes runs in no
    scope of its body (the carry's sums and copies) keeps ``pass``."""
    from autodist_tpu.graph_item import scope_path, strip_pass
    scope = "/".join(scope_path(op_name).split("/")[:-1])
    inner = strip_pass(scope)
    return [s for s in (scope if scope == "pass" else inner).split("/") if s]


def _scope_and_phase(op_name):
    """``(scope, phase)`` of one HLO ``op_name``.  The last segment of the
    name stack is the primitive, what precedes it the user's scopes."""
    segs = _user_scopes(op_name)
    scope = UNATTRIBUTED
    if segs:
        scope = collapse("/".join(segs))
        if segs[0] == MTP_SCOPE:
            segs = segs[1:] or segs
        if segs[0] in HEAD_SCOPES:
            scope = "head"
        elif len(segs) > 1 and segs[1] in BLOCK_SCOPES:
            scope = segs[1]
        elif len(segs) > 1 and segs[1] in (MOE_SCOPE, GDN_SCOPE, KDA_SCOPE):
            scope = "/".join(segs[1:4] if tuple(segs[1:4]) == ROUTER_GROUPS
                             else segs[1:3])
        elif segs[0] in UPDATE_SCOPES:
            scope = segs[0]
    if scope in UPDATE_SCOPES:
        phase = "update"
    elif "transpose(jvp(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = UNATTRIBUTED
    return scope, phase


_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"\bto_apply=%?([\w.\-]+)")
_OPCODE_RE = re.compile(r"\s([a-z][a-z\-]*)\(")
_OPERAND_RE = re.compile(r"\(\s*%?([\w.\-]+)")
#: The XLA TPU compiler's own asynchronous form: the collective sits in a
#: fusion beside one of these custom calls, ``...Start`` in the fusion that
#: begins it and ``...Done`` in the one that waits for it.
_ASYNC_MARK_RE = re.compile(r'custom_call_target="AsyncCollective(Start|Done)"')
#: Work of a computation's own (``kernel/overlap._COMPUTE_RE``'s opcodes and
#: the two that run computations of their own): a fusion or call that holds
#: one of these beside a collective is compute.
_COMPUTE_OPCODES = ("fusion", "dot", "convolution", "custom-call", "while",
                    "conditional")


def _parse_scopes(hlo_text, place=_scope_and_phase):
    """One pass over a compiled program's text: ``own`` maps every
    instruction to the ``(scope, phase)`` of its own ``op_name`` (None
    without one), ``calls`` maps a fusion (or an ``async-*`` wrapper) to the
    computation it calls, and ``votes`` counts, per computation, the
    ``(scope, phase)`` of its instructions that carry a named scope.
    ``place`` is what reads an ``op_name``.

    ``held`` says, per computation, what :func:`_communication` needs:
    ``seq`` lists in schedule order its collectives and the instructions
    that call a computation, each as ``(name, opcode, line, called
    computation or None, collective or None)``, a collective as ``(kind,
    half, payload bytes, group)`` with ``half`` one of ``-start``, ``-done``
    or None; ``busy`` is whether it holds work of its own
    (:data:`_COMPUTE_OPCODES`, the asynchronous markers apart), ``slice``
    whether it holds a ``dynamic-slice``, ``mark`` the half of the TPU
    compiler's asynchronous pair it holds, if any."""
    from autodist_tpu.kernel import overlap as ov
    own, calls, votes, held = {}, {}, {}, {}
    computation = rec = None
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "\t"):
            header = _COMPUTATION_RE.match(line)
            computation = header.group(1) if header else None
            rec = held.setdefault(computation, {
                "seq": [], "busy": False, "slice": False, "mark": None})
            continue
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            continue
        name = m.group(1)
        op = _OP_NAME_RE.search(line)
        placed = place(op.group(1)) if op else None
        own[name] = placed
        called = _CALLS_RE.search(line)
        if called:
            calls[name] = called.group(1)
        if placed and placed[0] != UNATTRIBUTED and computation:
            tally = votes.setdefault(computation, {})
            tally[placed] = tally.get(placed, 0) + 1
        found = _OPCODE_RE.search(line, m.end() - 1)
        if found is None:
            continue
        opcode = found.group(1)
        collective = ov._COLLECTIVE_RE.match(line, found.start())
        if collective:
            kind, half = collective.groups()
            group = ov._group_size(line)
            nbytes = ov._payload_bytes(
                kind, half, line[m.end():found.start()], group)
            rec["seq"].append((name, opcode, line, None,
                               (kind, half, nbytes, group)))
        elif called or opcode == "call":
            target = called or _TO_APPLY_RE.search(line)
            if target:
                rec["seq"].append((name, opcode, line, target.group(1),
                                   None))
        elif opcode == "custom-call":
            mark = _ASYNC_MARK_RE.search(line)
            if mark:
                rec["mark"] = mark.group(1).lower()
            else:
                rec["busy"] = True
        elif opcode in _COMPUTE_OPCODES:
            rec["busy"] = True
        elif opcode == "dynamic-slice":
            rec["slice"] = True
    return own, calls, votes, held


def _communication(calls, held):
    """``{instruction: {"kind", "bytes", "group", "async"}}`` for the
    communication instructions of a parsed program (:func:`comm_table`
    says what each key means): the collectives, and the fusions, calls and
    ``async-*`` wrappers whose computation holds collectives, directly or
    through such instructions of its own, and no work beside them.  An
    instruction inside a fused or wrapped computation never runs by itself
    and has no row."""
    carried = {}

    def carries(computation):
        """``[(kind, bytes, group)]`` of the collectives a computation
        holds where it does nothing else; ``()`` otherwise."""
        if computation not in carried:
            carried[computation] = ()       # a cycle carries nothing
            rec, found = held.get(computation), []
            for _, _, _, target, collective in \
                    rec["seq"] if rec and not rec["busy"] else ():
                if target:
                    inner = carries(target)
                    if not inner:
                        found = []          # a fusion of its own: compute
                        break
                    found += inner
                elif collective[1] != "-done":
                    kind, _, nbytes, group = collective
                    found.append((kind, nbytes, group))
            carried[computation] = tuple(found)
        return carried[computation]

    table, inlined = {}, set(calls.values())
    for computation, rec in held.items():
        if computation in inlined:
            continue
        begun = []      # the TPU compiler's pairs: starts not yet waited for
        for name, opcode, line, target, collective in rec["seq"]:
            mark = None
            if target is None:
                kind, half, nbytes, group = collective
            else:
                inner = carries(target)
                if not inner:
                    continue
                kind, _, group = max(inner, key=lambda c: c[1])
                nbytes = sum(c[1] for c in inner)
                if kind == "all-reduce" and held[target]["slice"]:
                    kind = "reduce-scatter"     # the compiler's fused form
                mark = held[target]["mark"]
                half = "-start" if opcode.endswith("-start") else \
                    "-done" if opcode.startswith("async-") else None
            pair = False
            if half == "-start" or mark == "start":
                pair = name
                if mark:
                    begun.append(name)
            elif mark == "done" and begun:
                pair = begun.pop()
            elif half == "-done":
                # Named by its operand; an ``async-update`` passes it on.
                pair = _OPERAND_RE.search(line, line.index(opcode + "("))
                pair = pair.group(1) if pair else name
                pair = table.get(pair, {}).get("async") or pair
            if pair and pair != name:
                # The half that began it carries the bytes and the group.
                nbytes, group = 0, table.get(pair, {}).get("group", group)
            table[name] = {"kind": kind, "bytes": nbytes, "group": group,
                           "async": pair}
    return table


def scope_table(hlo_text):
    """``{instruction name: (scope, phase)}`` from a compiled program's
    text, by the ``op_name`` metadata.  Pure.

    A scope is the ``jax.named_scope`` path an instruction was traced
    under, capped at :data:`SCOPE_DEPTH`, with ``layer<i>/attn`` of every
    ``i`` folded into ``attn`` (likewise ``mlp``; ``layer<i>/moe/router``
    into ``moe/router``, likewise ``moe/dispatch``, ``moe/experts``) and
    ``logits`` / ``lm_head`` / ``mlm_head`` (the loss is traced inside
    them) into ``head``.  The phase is ``backward`` where the name passes through
    ``transpose(jvp(``, ``forward`` under ``jvp(`` alone, ``update`` for
    the Runner's own scopes (:data:`UPDATE_SCOPES`).

    A fusion runs as one instruction, and the ``op_name`` it carries itself
    is that of one instruction it fused, not necessarily the one that does
    its work (XLA fuses each weight's Adam update into the matmul that
    makes its gradient, and the fusion keeps the matmul's name).  So a
    fusion is placed where most of the scoped instructions of the
    computation it calls are (:func:`mixed_fusions` lists those that hold
    more than one scope); its own name decides a tie and a computation
    with no scope in it.  An instruction with no ``op_name``, or with none
    of the user's scopes in it, is :data:`UNATTRIBUTED` — surfaced, never
    absorbed.

    Communication is placed by what the instruction is, where its name says
    nothing.  A communication instruction (a row of :func:`comm_table`: a
    collective, or a fusion, call or ``async-*`` wrapper whose computation
    holds collectives and no work beside them) that has a named scope by
    the reading above keeps it.  The compiler's rewrites (a combined
    ``all-reduce``, the ``all-reduce`` + ``dynamic-slice`` fusion a TPU
    makes of a reduce-scatter) drop the ``op_name``, and one with no named
    scope goes by :data:`COMM_SCOPE_OF_KIND`: ``grad_sync`` if it reduces
    (all-reduce, reduce-scatter and that fused form), ``param_gather`` if
    it gathers (all-gather), phase ``update``; a scope-less
    ``collective-permute`` or ``all-to-all`` stays :data:`UNATTRIBUTED`.
    On the Runner's explicit step the rule is exact: every reduction there
    is a gradient's but the mean of loss and ``aux``, which runs under
    ``loss_sync`` and keeps that name.  Nothing else moves: every
    instruction that has a scope by its ``op_name`` or by the vote has the
    same one with this rule as without.
    """
    return _tables(hlo_text)[0]


def _tables(hlo_text):
    """``(scope table, communication rows without their scopes)`` of one
    parse."""
    own, calls, votes, held = _parse_scopes(hlo_text)
    table, comm = _voted(own, calls, votes), _communication(calls, held)
    for name, row in comm.items():
        if table[name][0] == UNATTRIBUTED and \
                row["kind"] in COMM_SCOPE_OF_KIND:
            table[name] = (COMM_SCOPE_OF_KIND[row["kind"]], "update")
    return table, comm


def comm_table(hlo_text):
    """``{instruction name: {"kind", "bytes", "group", "async", "scope"}}``
    for every communication instruction of a compiled program's text.  Pure.

    A communication instruction is a collective (``all-reduce``,
    ``reduce-scatter``, ``all-gather``, ``collective-permute``,
    ``all-to-all``, their ``-start`` and ``-done`` halves) or a fusion,
    call or ``async-*`` wrapper whose computation holds collectives,
    directly or through such instructions of its own, and no work beside
    them.  A fusion that holds a collective AND work of its own (the TPU
    compiler's ``async_collective_fusion``: a matrix product with a step of
    an all-gather riding in it) is compute and has no row; the gather it
    carries is in flight between the two fusions that hold the
    ``AsyncCollectiveStart`` and ``AsyncCollectiveDone`` markers, which have.

    ``kind`` is the collective's (``reduce-scatter`` for a fusion that holds
    an ``all-reduce`` and a ``dynamic-slice``, the form a TPU gives it).
    ``bytes`` is the payload, the whole array reduced, gathered or moved,
    from the instruction's shapes (a fusion's: its collectives' summed;
    padding the compiler added is in it, being on the wire).  ``group`` is
    the replica group's size.  ``async`` is False where the instruction
    runs synchronously on the core; for a half of an asynchronous pair it is
    the name of the instruction that began the pair (its own name for that
    one), and the half that began it carries the bytes: a ``-done`` half's
    are 0, so that the rows sum to the step's traffic.  ``scope`` is where
    :func:`scope_table` places the instruction.

    Built on ``kernel/overlap``'s expressions and helpers (which price the
    same text on a cost model for ``comms.exposed_ms_per_step``: the
    prediction; :func:`comm_time` over a trace is the measurement).
    """
    table, comm = _tables(hlo_text)
    return {name: dict(row, scope=table[name][0])
            for name, row in comm.items()}


def comm_wire_bytes(table, by="kind"):
    """``{kind: bytes}`` a chip sends in one run of the program, by a
    :func:`comm_table` (``by="scope"``: ``{scope: bytes}``): in a ring over
    ``group`` chips a reduce-scatter, an all-gather or an all-to-all sends
    ``(group - 1) / group`` of its payload, an all-reduce (a reduce-scatter
    then an all-gather) twice that, and a collective-permute its payload
    once."""
    out = {}
    for row in table.values():
        ring = 1.0 if row["kind"] == "collective-permute" else \
            (row["group"] - 1) / max(1, row["group"])
        if row["kind"] == "all-reduce":
            ring *= 2
        out[row[by]] = out.get(row[by], 0.0) + ring * row["bytes"]
    return out


def _voted(own, calls, votes):
    """:func:`scope_table`'s placement: an instruction's own reading, a
    fusion's by the vote of what it fused."""
    table = {}
    for name, placed in own.items():
        tally = votes.get(calls.get(name), {})
        if tally:
            best = max(tally.values())
            winners = [k for k, v in tally.items() if v == best]
            placed = placed if placed in winners else winners[0]
        table[name] = placed or (UNATTRIBUTED, UNATTRIBUTED)
    return table


def overlay_table(hlo_text, top_scope):
    """``{instruction name: (where, phase)}`` with ``where`` one of
    ``top_scope``, ``"elsewhere"`` and :data:`UNATTRIBUTED`: which
    instructions were traced under the top-level named scope ``top_scope``
    whatever :func:`scope_table` folds them into, a fusion again by the
    vote of what it fused.  Joined with a trace
    (:func:`device_time_by_scope`) it gives the module's whole time as an
    overlay on the generic rows (``mtp``: the prediction module)."""
    from autodist_tpu.graph_item import scope_path

    def place(op_name):
        segs = scope_path(op_name).split("/")[:-1]
        where = UNATTRIBUTED if not segs else \
            top_scope if segs[0] == top_scope else "elsewhere"
        return where, _scope_and_phase(op_name)[1]
    return _voted(*_parse_scopes(hlo_text, place)[:3])


def subscope_table(hlo_text, block_scope):
    """``{instruction name: (where, phase)}`` with ``where`` the block
    sub-scope ``block_scope`` one level further down than
    :func:`scope_table` folds it: ``layer<i>/attn/window_core`` of every
    ``i`` (and the prediction module's block's) is ``attn/window_core``
    for ``block_scope="attn"``; what is traced under ``block_scope`` and
    in none of its inner scopes is ``block_scope``, everything else
    ``"elsewhere"`` or :data:`UNATTRIBUTED`; a fusion again by the vote of
    what it fused.  Joined with a trace (:func:`device_time_by_scope`) it
    splits one row of the generic table (``attn``: ``qkv``, ``rope``,
    ``core`` / ``window_core``, ``gate``, ``out`` of ``layers.mha``)."""
    def place(op_name):
        segs = _user_scopes(op_name)
        if segs and segs[0] == MTP_SCOPE:
            segs = segs[1:] or segs
        where = UNATTRIBUTED if not segs else "elsewhere"
        if len(segs) > 1 and segs[1] == block_scope:
            where = "/".join(segs[1:3])
        return where, _scope_and_phase(op_name)[1]
    return _voted(*_parse_scopes(hlo_text, place)[:3])


def mixed_fusions(hlo_text):
    """``{fusion name: {scope: scoped instructions}}`` for the fusions whose
    computation holds instructions of more than one scope: what
    :func:`scope_table` had to place by a vote."""
    _, calls, votes, _ = _parse_scopes(hlo_text)
    mixed = {}
    for name, computation in calls.items():
        scopes = {}
        for (scope, _), n in votes.get(computation, {}).items():
            scopes[scope] = scopes.get(scope, 0) + n
        if len(scopes) > 1:
            mixed[name] = scopes
    return mixed


def device_time_by_scope(events, table):
    """Join a device trace's ``(instruction name, start, end)`` events with
    a :func:`scope_table`: ``{"scope": {scope: seconds}, "phase": {phase:
    seconds}}``.  An event whose instruction the table does not hold goes
    to :data:`UNATTRIBUTED`, so each side sums to the events' total."""
    out = {"scope": {}, "phase": {}}
    missing = (UNATTRIBUTED, UNATTRIBUTED)
    for name, start, end in events:
        scope, phase = table.get(name, missing)
        out["scope"][scope] = out["scope"].get(scope, 0.0) + (end - start)
        out["phase"][phase] = out["phase"].get(phase, 0.0) + (end - start)
    return out


def _union_s(intervals):
    """Seconds the ``(start, end)`` intervals cover together."""
    covered, reach = 0.0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            covered, reach = covered + (hi - lo), hi
        elif hi > reach:
            covered, reach = covered + (hi - reach), hi
    return covered


def comm_time(ops, async_ops, table):
    """One chip's communication time from its trace, by a
    :func:`comm_table`: ``{"comm_s", "exposed_s", "by_kind": {kind:
    seconds}, "by_scope": {scope: seconds}}``.  Pure.

    ``ops`` are the ``(instruction name, start, end)`` events of the chip's
    line of operations, ``async_ops`` those of its line of asynchronous
    operations (an event there is named by the pair's first half).  A
    synchronous communication instruction is busy for its event.  A pair is
    in flight from the start of its first half to the end of its last: by
    its event among ``async_ops`` where the profiler wrote one, and by
    joining its halves' events among ``ops`` in order (so a chip whose
    asynchronous line is missing, or a pair the profiler keeps off it, is
    still whole).  ``comm_s`` is the union of all of these; ``exposed_s``
    the part of that union during which no instruction outside the table
    runs on the chip, so a synchronous collective, alone on the core, is
    exposed whole; ``by_kind`` and ``by_scope`` are the unions of each
    kind's and each scope's.
    """
    found, other, began = [], [], {}
    for name, lo, hi in sorted(ops, key=lambda e: e[1]):
        row = table.get(name)
        if row is None:
            other.append((lo, hi))
        elif not row["async"]:
            found.append((row, lo, hi))
        elif row["async"] == name:
            began[name] = lo
            found.append((row, lo, hi))
        else:
            found.append((row, began.pop(row["async"], lo), hi))
    found += [(table[name], lo, hi) for name, lo, hi in async_ops
              if name in table]
    out = {"comm_s": _union_s((lo, hi) for _, lo, hi in found),
           "by_kind": {}, "by_scope": {}}
    for key in ("kind", "scope"):
        for value in {row[key] for row, _, _ in found}:
            out["by_" + key][value] = _union_s(
                (lo, hi) for row, lo, hi in found if row[key] == value)
    # |comm and not other| = |comm or other| - |other|.
    out["exposed_s"] = _union_s([(lo, hi) for _, lo, hi in found] + other) \
        - _union_s(other)
    return out


# ---------------------------------------------------------------------------
# the profile object: measured structure + model predictions


class Profile:
    """Per-scope cost structure for one program.

    ``measured`` carries the best-available per-scope structure (HLO when
    recorded, else the model costs), ``predicted`` always the model
    costs; ``sources`` records which is which per cost class —
    measured-vs-predicted deltas are only meaningful when the measured
    side really is a measurement (same honesty rule as the ledger).
    """

    def __init__(self, measured, predicted, sources, unroll=1):
        self.measured = measured
        self.predicted = predicted
        self.sources = dict(sources)
        self.unroll = max(1, int(unroll))

    def reconcile(self, attr_summary):
        """Normalize per-scope shares against the step ledger so the
        per-scope sums equal the ledger's terms EXACTLY:

        * compute rows sum to ``attr.device_compute_ms``;
        * comms rows sum to ``attr.exposed_comms_ms``;
        * whatever share no scope claims stays in ``(unattributed)``.

        Without a ledger summary (no observed loop yet) the raw model
        units are kept and ``reconciled`` is marked ``False``.
        """
        attr = attr_summary or {}
        ledger = {"compute_ms": attr.get("device_compute_ms"),
                  "comms_ms": attr.get("exposed_comms_ms")}
        total = {cls: sum(rec[cls] for rec in self.measured.values())
                 for cls in ("compute_ms", "comms_ms")}
        scale = {}
        for cls in ("compute_ms", "comms_ms"):
            if ledger[cls] is None:
                scale[cls] = 1.0
            elif total[cls] > 0:
                scale[cls] = ledger[cls] / total[cls]
            else:
                scale[cls] = 0.0
        rows = {}
        for scope in set(self.measured) | set(self.predicted):
            m = self.measured.get(scope, _zero())
            p = self.predicted.get(scope, _zero())
            rows[scope] = {
                "compute_ms": round(m["compute_ms"] * scale["compute_ms"], 6),
                "comms_ms": round(m["comms_ms"] * scale["comms_ms"], 6),
                "wire_bytes": round(m["wire_bytes"] or p["wire_bytes"], 1),
                "predicted_compute_ms": round(p["compute_ms"], 6),
                "predicted_comms_ms": round(p["comms_ms"], 6),
                "ops": m["ops"] or p["ops"],
            }
        # The ledger total that no measured row carried (e.g. zero
        # model/HLO structure but a nonzero ledger term) is remainder —
        # it lands in the unattributed row, never disappears.
        for cls in ("compute_ms", "comms_ms"):
            if ledger[cls] is not None and total[cls] <= 0 and ledger[cls]:
                rows.setdefault(UNATTRIBUTED, dict(_zero()))
                rows[UNATTRIBUTED][cls] = round(ledger[cls], 6)

        named = {s: r for s, r in rows.items() if s != UNATTRIBUTED}
        unatt = rows.get(UNATTRIBUTED, _zero())
        tot_c = sum(r["compute_ms"] for r in rows.values())
        tot_m = sum(r["comms_ms"] for r in rows.values())
        attributed = sum(r["compute_ms"] + r["comms_ms"]
                         for r in named.values())
        coverage = 100.0 * attributed / (tot_c + tot_m) \
            if (tot_c + tot_m) > 0 else 0.0
        top = sorted(named, key=lambda s: -(named[s]["compute_ms"] +
                                            named[s]["comms_ms"]))
        return {
            "scopes": named,
            "unattributed": {k: unatt[k] for k in
                             ("compute_ms", "comms_ms", "wire_bytes")},
            "totals": {"compute_ms": round(tot_c, 6),
                       "comms_ms": round(tot_m, 6),
                       "wire_bytes": round(sum(r["wire_bytes"]
                                               for r in rows.values()), 1)},
            "coverage_pct": round(coverage, 2),
            "top": top[:TOPK],
            "sources": dict(self.sources),
            "reconciled": any(ledger[c] is not None
                              for c in ("compute_ms", "comms_ms")),
            "unroll": self.unroll,
            "steps": attr.get("steps"),
        }


def profile_runner(runner, unroll=1):
    """Build the per-scope profile for one Runner's program.

    The model-side costs are always the prediction; when the AOT path
    stashed a scheduled HLO text (``Runner._record_exposed_comms``), a
    cost class whose HLO attribution found at least one named scope is
    upgraded to the measured instruction stream — classes the HLO left
    fully unattributed keep the provenance-rich model structure (the
    grad collectives are emitted by the runner's sync code, outside any
    model scope, so comms usually stays model-attributed).
    """
    predicted, known = model_scope_costs(runner, unroll=unroll)
    measured = {s: dict(rec) for s, rec in predicted.items()}
    sources = {"compute": "jaxpr-flops", "comms": "strategy-model"}
    stashed = getattr(runner, "_scheduled_hlo_text", None)
    if stashed:
        text, hlo_unroll = stashed
        try:
            hlo = hlo_scope_costs(text, known, unroll=hlo_unroll)
            for cls in ("compute_ms", "comms_ms"):
                if not any(rec[cls] for s, rec in hlo.items()
                           if s != UNATTRIBUTED):
                    continue
                src = "compute" if cls == "compute_ms" else "comms"
                sources[src] = "scheduled-hlo"
                for rec in measured.values():
                    rec[cls] = 0.0
                    if cls == "comms_ms":
                        rec["wire_bytes"] = 0.0
                for s, rec in hlo.items():
                    row = measured.setdefault(s, _zero())
                    row[cls] += rec[cls]
                    if cls == "comms_ms":
                        row["wire_bytes"] += rec["wire_bytes"]
        except Exception as e:  # noqa: BLE001 - fall back to model costs
            logging.debug("HLO scope costs unavailable: %s", e)
    return Profile(measured, predicted, sources, unroll=unroll)


# ---------------------------------------------------------------------------
# finalize: gauges, sidecar, calibration feed


def feed_calibration(summary, calibration=None):
    """Per-scope measured-vs-predicted observations for the tuner.

    Only classes whose measured side came from the scheduled HLO teach
    anything (model-vs-itself is a constant ratio); the worst top-K
    offenders are folded as per-class ``observe_term`` samples with a
    ``profile:<scope>`` context — the per-op cost record ROADMAP item
    3's searcher reads back.
    """
    if not summary:
        return None
    sources = summary.get("sources") or {}
    if not any(v == "scheduled-hlo" for v in sources.values()):
        return None
    try:
        if calibration is None:
            from autodist_tpu.tuner.calibration import Calibration
            calibration = Calibration.load()
        rows = summary.get("scopes") or {}
        offenders = sorted(
            rows, key=lambda s: -max(
                abs(rows[s]["compute_ms"] - rows[s]["predicted_compute_ms"]),
                abs(rows[s]["comms_ms"] - rows[s]["predicted_comms_ms"])))
        for scope in offenders[:TOPK]:
            r = rows[scope]
            if sources.get("compute") == "scheduled-hlo" and \
                    r["predicted_compute_ms"] > 0 and r["compute_ms"] > 0:
                calibration.observe_term(
                    "compute", r["predicted_compute_ms"], r["compute_ms"],
                    context=f"profile:{scope}")
            if sources.get("comms") == "scheduled-hlo" and \
                    r["predicted_comms_ms"] > 0 and r["comms_ms"] > 0:
                calibration.observe_term(
                    "comms", r["predicted_comms_ms"], r["comms_ms"],
                    context=f"profile:{scope}")
        return calibration
    except Exception as e:  # noqa: BLE001 - calibration is best-effort
        logging.debug("profile calibration feed failed: %s", e)
        return None


def finalize(profile, attr_summary, registry=None):
    """End-of-run bookkeeping: reconcile against the ledger, publish the
    ``profile.*`` gauges, stash the summary for monitor and report,
    write the ``profile.json`` sidecar under ``AUTODIST_DUMP_GRAPHS``,
    and feed the per-class calibration."""
    summary = profile.reconcile(attr_summary)
    if registry is not None:
        named = summary["scopes"]
        registry.gauge("profile.scopes").set(len(named))
        registry.gauge("profile.coverage_pct").set(summary["coverage_pct"])
        registry.gauge("profile.unattributed_ms").set(round(
            summary["unattributed"]["compute_ms"] +
            summary["unattributed"]["comms_ms"], 6))
        if summary["top"]:
            hot = summary["top"][0]
            registry.gauge("profile.top_compute_ms").set(
                named[hot]["compute_ms"])
            registry.gauge("profile.top_comms_ms").set(
                max(r["comms_ms"] for r in named.values()))
    set_last_profile(summary)
    feed_calibration(summary)
    if const.ENV.AUTODIST_DUMP_GRAPHS.val:
        try:
            const.ensure_working_dirs()
            path = os.path.join(const.DEFAULT_GRAPH_DUMP_DIR, "profile.json")
            with open(path, "w") as f:
                json.dump(summary, f, indent=1, sort_keys=True)
        except OSError as e:
            logging.debug("profile sidecar not written: %s", e)
    try:
        from autodist_tpu.observability import recorder
        hot = summary["top"][0] if summary["top"] else "(none)"
        recorder.record(
            "profile",
            f"{len(summary['scopes'])} scopes, {summary['coverage_pct']:.0f}%"
            f" attributed, hottest {hot}")
    except Exception:  # noqa: BLE001 - telemetry must never kill a run
        pass
    return summary


def last_summary_rows(limit=None):
    """Top-N ``(scope, row)`` pairs of the last profile (monitor/report
    convenience); ``[]`` before the first profiled run."""
    summ = last_profile()
    if not summ:
        return []
    rows = summ["scopes"]
    order = summ.get("top") or sorted(
        rows, key=lambda s: -(rows[s]["compute_ms"] + rows[s]["comms_ms"]))
    extra = [s for s in rows if s not in order]
    ranked = list(order) + sorted(
        extra, key=lambda s: -(rows[s]["compute_ms"] + rows[s]["comms_ms"]))
    return [(s, rows[s]) for s in ranked[:limit or TOPK]]


def last_profile():
    """The most recent finalized per-layer profile in this process."""
    return _last_profile


def set_last_profile(summary):
    global _last_profile
    _last_profile = summary


def reset():
    """Test harness hook."""
    set_last_profile(None)
