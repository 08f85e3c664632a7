"""The compiled step's communication, named and timed by the program
(docs/observability.md, "Reading a multi-chip trace"): ``profile.comm_table``
finds every collective of the Runner's explicit step and the compiler's fused
forms of them, ``profile.scope_table`` places what carries no name by what it
is and moves nothing else, ``profile.comm_time`` joins the table with one
chip's events, and none of it compiles anything."""
import collections
import gzip
import json
import pathlib

import jax
import optax
import pytest

from autodist_tpu import AutoDist, observability, strategy
from autodist_tpu.kernel import overlap
from autodist_tpu.models import lm
from autodist_tpu.observability import profile
from autodist_tpu.observability.profile import UNATTRIBUTED

# A cut of the v5e's compiled text of ``gpt2-xl.train-s1024-x4``'s step (the
# instructions of seven stretches of one traced step, with the computations
# they call) and the events of those instructions on chip 0 in that step
# (my chip run, PR 33; ``[name, start, end]`` in ns from the step's start).
RECORDED = str(pathlib.Path(__file__).resolve().parent / "chipbench" / "data"
               / "gpt2-xl.train-s1024-x4.pr33")
LOWERINGS = {"explicit": "PartitionedPS", "gspmd": "AllReduce"}


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    observability.refresh()
    observability.reset()
    yield
    observability.refresh()
    observability.reset()


def _counter(name):
    return observability.registry().snapshot()["counters"].get(name, 0)


def _stepped(lowering, chips=None):
    """A session that has run one step, on ``chips`` of the CPU's devices
    (all eight without)."""
    params, loss_fn, batch = lm.tiny_fixture()
    ad = AutoDist(strategy_builder=getattr(strategy, LOWERINGS[lowering])(),
                  mesh_axes={"data": chips} if chips else None,
                  devices=jax.devices()[:chips] if chips else None)
    item = ad.capture(loss_fn, params, optax.adam(1e-4), example_batch=batch)
    runner = ad.create_distributed_session(item)
    assert runner.program.use_explicit_path == (lowering == "explicit")
    runner.step(runner.create_state(), batch)
    return runner


# -- the Runner's own step ----------------------------------------------------


def test_comm_table_finds_every_collective_of_the_explicit_step():
    runner = _stepped("explicit", chips=4)
    assert runner.program.data_axis_size == 4
    compiles = _counter("compile.count")
    misses = _counter("compile.cache_misses")
    table = runner.comm_table()
    # Nothing new compiles: the executable is the one JAX already holds.
    assert _counter("compile.count") == compiles
    assert _counter("compile.cache_misses") == misses
    # PartitionedPS keeps every variable of the toy as its shard (``fsdp``):
    # one all_gather a variable on the way in, and its transpose, one
    # psum_scatter a variable, on the way back.
    item = runner.program.graph_item
    kinds = {name: kind for name, (kind, _) in runner.var_kinds.items()}
    assert set(kinds.values()) == {"fsdp"}
    by_kind = collections.defaultdict(list)
    for row in table.values():
        by_kind[row["kind"]].append(row)
    assert len(by_kind["all-gather"]) == len(kinds)
    assert len(by_kind["reduce-scatter"]) == len(kinds)
    variables = sum(v.size_bytes for v in item.variables)
    assert sum(r["bytes"] for r in by_kind["all-gather"]) == variables
    assert sum(r["bytes"] for r in by_kind["reduce-scatter"]) == variables
    # The one reduction that is no gradient's keeps its own name.
    (loss,) = by_kind["all-reduce"]
    assert loss["scope"] == "loss_sync" and loss["bytes"] == 4
    assert {r["scope"] for r in by_kind["all-gather"]
            + by_kind["reduce-scatter"]} == {"param_gather"}
    # The CPU runs every collective synchronously, over the four devices.
    assert {(r["group"], r["async"]) for r in table.values()} == {(4, False)}
    wire = profile.comm_wire_bytes(table)
    assert wire == {"all-gather": pytest.approx(0.75 * variables),
                    "reduce-scatter": pytest.approx(0.75 * variables),
                    "all-reduce": pytest.approx(2 * 0.75 * 4)}
    # Every row is an instruction of the scope table, placed the same.
    scopes = runner.scope_table()
    assert {name: row["scope"] for name, row in table.items()} == {
        name: scopes[name][0] for name in table}
    assert ("loss_sync", "update") in set(scopes.values())


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_the_placement_rule_moves_only_what_had_no_scope(lowering):
    """The table with the rule against the table without it (an
    instruction's own reading, a fusion's by the vote: what the function
    did before it knew communication)."""
    text = _stepped(lowering).step_text()
    before = profile._voted(*profile._parse_scopes(text)[:3])
    after, rows = profile.scope_table(text), profile.comm_table(text)
    assert set(before) == set(after)
    moved = {name for name in after if after[name] != before[name]}
    assert moved <= set(rows)
    assert all(before[name] == (UNATTRIBUTED, UNATTRIBUTED)
               and after[name] == (profile.COMM_SCOPE_OF_KIND[
                   rows[name]["kind"]], "update") for name in moved)
    if lowering == "gspmd":
        # GSPMD's own collectives carry the name of what they serve; the
        # compiler's scope-less ones are placed by kind.
        assert rows and not any(r["scope"] == "loss_sync"
                                for r in rows.values())


# -- a hand-written text: the rule's three branches ---------------------------

TEXT = "\n".join([
    "HloModule jit_local_step, is_scheduled=true",
    "%region_add (a: f32[], b: f32[]) -> f32[] {",
    "  %a = f32[] parameter(0)",
    "  %b = f32[] parameter(1)",
    "  ROOT %add.1 = f32[] add(%a, %b)",
    "}",
    # The TPU compiler's fused reduce-scatter: no op_name anywhere.
    "%all-reduce-scatter.3 (input: f32[30,8]) -> f32[8,8] {",
    "  %input = f32[30,8]{1,0} parameter(0)",
    "  %constant.1 = f32[] constant(0)",
    "  %pad.4 = f32[32,8]{1,0} pad(%input, %constant.1), padding=0_2x0_0",
    "  %all-reduce.7 = f32[32,8]{1,0} all-reduce(%pad.4), channel_id=7, "
    "replica_groups={{0,1,2,3}}, to_apply=%region_add",
    "  %partition-id.1 = u32[] partition-id()",
    "  ROOT %dynamic-slice.2 = f32[8,8]{1,0} dynamic-slice(%all-reduce.7, "
    "%partition-id.1, %constant.1), dynamic_slice_sizes={8,8}",
    "}",
    # Its asynchronous all-gather: a fusion that begins it, a matrix
    # product that carries a step of it, a fusion that waits for it.
    "%fused_computation.10 (p: bf16[4,8]) -> (bf16[4,8], bf16[16,8]) {",
    "  %p.1 = bf16[4,8]{1,0} parameter(0)",
    '  %all-gather.20 = bf16[16,8]{1,0} all-gather(%p.1), channel_id=1, '
    'replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name='
    '"jit(local_step)/shard_map/jvp(param_gather)/all_gather"}',
    "  ROOT %custom-call.5 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) custom-call("
    '%all-gather.20), custom_call_target="AsyncCollectiveStart"',
    "}",
    "%async_collective_fusion.11 (p: bf16[4,8], x: bf16[2,8]) -> bf16[2,8] {",
    "  %p.2 = bf16[4,8]{1,0} parameter(0)",
    "  %x.2 = bf16[2,8]{1,0} parameter(1)",
    '  %convolution.3 = bf16[2,8]{1,0} convolution(%x.2, %x.2), metadata={'
    'op_name="jit(local_step)/shard_map/jvp(layer0)/attn/dot_general"}',
    '  %all-gather.21 = bf16[16,8]{1,0} all-gather(%p.2), channel_id=1, '
    'replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name='
    '"jit(local_step)/shard_map/jvp(param_gather)/all_gather"}',
    "  ROOT %tuple.9 = (bf16[2,8]{1,0}, bf16[16,8]{1,0}) tuple("
    "%convolution.3, %all-gather.21)",
    "}",
    "%fused_computation.12 (p: bf16[4,8]) -> bf16[16,8] {",
    "  %p.3 = bf16[4,8]{1,0} parameter(0)",
    '  %all-gather.22 = bf16[16,8]{1,0} all-gather(%p.3), channel_id=1, '
    'replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name='
    '"jit(local_step)/shard_map/jvp(param_gather)/all_gather"}',
    "  ROOT %custom-call.6 = bf16[16,8]{1,0} custom-call(%p.3, "
    '%all-gather.22), custom_call_target="AsyncCollectiveDone"',
    "}",
    # XLA's generic asynchronous wrapper.
    "%wrapped_all_to_all (p: f32[4,8]) -> f32[4,8] {",
    "  %p.4 = f32[4,8]{1,0} parameter(0)",
    "  ROOT %all-to-all.1 = f32[4,8]{1,0} all-to-all(%p.4), channel_id=9, "
    "replica_groups=[1,4]<=[4], dimensions={0}",
    "}",
    "ENTRY %main (s: f32[8,8]) -> f32[8,8] {",
    "  %s = f32[8,8]{1,0} parameter(0)",
    # 1. Its own name keeps an instruction where it is, whatever it is.
    '  %all-reduce.1 = f32[8,8]{1,0} all-reduce(%s), channel_id=2, '
    'replica_groups={{0,1,2,3}}, to_apply=%region_add, metadata={op_name='
    '"jit(local_step)/shard_map/loss_sync/psum"}',
    '  %reduce-scatter.2 = f32[2,8]{1,0} reduce-scatter(%s), channel_id=3, '
    'replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%region_add, '
    'metadata={op_name="jit(local_step)/shard_map/transpose(jvp('
    'param_gather))/reduce_scatter"}',
    '  %collective-permute.3 = f32[8,8]{1,0} collective-permute(%s), '
    'channel_id=4, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, metadata='
    '{op_name="jit(local_step)/shard_map/jvp(layer1)/attn/ppermute"}',
    # 2. With no name: by what it does.  A combined all-reduce is a tuple.
    "  %all-reduce.4 = (f32[8]{0}, bf16[8]{0}, /*index=2*/f32[8,8]{1,0}) "
    "all-reduce(%s, %s, %s), channel_id=5, replica_groups={{0,1,2,3}}, "
    "to_apply=%region_add",
    "  %fusion.5 = f32[8,8]{1,0} fusion(%s), kind=kCustom, "
    "calls=%all-reduce-scatter.3",
    "  %all-gather-start.6 = (f32[2,8]{1,0}, f32[8,8]{1,0}) "
    "all-gather-start(%s), channel_id=6, replica_groups=[1,4]<=[4], "
    "dimensions={0}",
    "  %async-collective-start = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) fusion("
    "%s), kind=kCustom, calls=%fused_computation.10",
    "  %fusion.8 = bf16[2,8]{1,0} fusion(%s, %s), kind=kOutput, "
    'calls=%async_collective_fusion.11, metadata={op_name='
    '"jit(local_step)/shard_map/jvp(layer0)/attn/dot_general"}',
    "  %all-gather-done.6 = f32[8,8]{1,0} all-gather-done("
    "%all-gather-start.6)",
    "  %async-collective-done = bf16[16,8]{1,0} fusion(%s), kind=kCustom, "
    'calls=%fused_computation.12, metadata={op_name='
    '"jit(local_step)/shard_map/jvp(param_gather)/all_gather"}',
    # 3. A scope-less permute or all-to-all is left where it was.
    "  %collective-permute-start.9 = (f32[3,8]{1,0}, f32[3,8]{1,0}, u32[], "
    "u32[]) collective-permute-start(%s), channel_id=8, "
    "source_target_pairs={{0,1},{1,2},{2,3}}",
    "  %collective-permute-done.9 = f32[3,8]{1,0} collective-permute-done("
    "%collective-permute-start.9)",
    "  %all-to-all-start = ((f32[4,8]{1,0}), f32[4,8]{1,0}, u32[]) "
    "async-start(%s), calls=%wrapped_all_to_all",
    "  %all-to-all-done = f32[4,8]{1,0} async-done(%all-to-all-start), "
    "calls=%wrapped_all_to_all",
    "  ROOT %copy.1 = f32[8,8]{1,0} copy(%s)",
    "}"])


def _row(kind, nbytes, scope, pair=False, group=4):
    return {"kind": kind, "bytes": nbytes, "group": group, "async": pair,
            "scope": scope}


def test_comm_table_and_the_rule_on_a_hand_written_text():
    assert profile.comm_table(TEXT) == {
        "all-reduce.1": _row("all-reduce", 256, "loss_sync"),
        "reduce-scatter.2": _row("reduce-scatter", 256, "param_gather"),
        "collective-permute.3": _row("collective-permute", 256, "attn",
                                     group=1),
        "all-reduce.4": _row("all-reduce", 32 + 16 + 256, "grad_sync"),
        # The padded array is what is on the wire.
        "fusion.5": _row("reduce-scatter", 32 * 8 * 4, "grad_sync"),
        "all-gather-start.6": _row("all-gather", 256, "param_gather",
                                   "all-gather-start.6"),
        "all-gather-done.6": _row("all-gather", 0, "param_gather",
                                  "all-gather-start.6"),
        "async-collective-start": _row("all-gather", 256, "param_gather",
                                       "async-collective-start"),
        "async-collective-done": _row("all-gather", 0, "param_gather",
                                      "async-collective-start"),
        "collective-permute-start.9": _row(
            "collective-permute", 96, UNATTRIBUTED,
            "collective-permute-start.9", group=1),
        "collective-permute-done.9": _row(
            "collective-permute", 0, UNATTRIBUTED,
            "collective-permute-start.9", group=1),
        "all-to-all-start": _row("all-to-all", 128, UNATTRIBUTED,
                                 "all-to-all-start"),
        "all-to-all-done": _row("all-to-all", 0, UNATTRIBUTED,
                                "all-to-all-start")}
    table = profile.scope_table(TEXT)
    assert table["all-reduce.1"] == ("loss_sync", "update")
    assert table["collective-permute.3"] == ("attn", "forward")
    assert table["all-reduce.4"] == ("grad_sync", "update")
    assert table["fusion.5"] == ("grad_sync", "update")
    assert table["all-gather-done.6"] == ("param_gather", "update")
    # A matrix product that carries a step of a gather is compute.
    assert table["fusion.8"] == ("attn", "forward")
    assert table["collective-permute-done.9"] == (UNATTRIBUTED, UNATTRIBUTED)
    assert table["copy.1"] == (UNATTRIBUTED, UNATTRIBUTED)
    # What is inside a fusion never runs by itself.
    assert table["all-reduce.7"] == (UNATTRIBUTED, UNATTRIBUTED)
    assert profile.comm_wire_bytes(profile.comm_table(TEXT)) == {
        "all-reduce": 2 * 0.75 * (256 + 304), "reduce-scatter": 0.75 * 1280,
        "collective-permute": 256 + 96, "all-gather": 0.75 * 512,
        "all-to-all": 0.75 * 128}
    assert profile.comm_wire_bytes(profile.comm_table(TEXT), by="scope") == {
        "loss_sync": 384.0, "param_gather": 3 * 192.0, "attn": 256.0,
        "grad_sync": 1.5 * 304 + 0.75 * 1024, UNATTRIBUTED: 96 + 96.0}
    # The modelled gauge reads the same helpers and the same text as before:
    # it prices the asynchronous pairs and the synchronous collectives that
    # carry their own opcode, and not yet the fused forms (ROADMAP D3).
    assert [r["name"] for r in overlap.async_collective_windows(TEXT)] == [
        "all-gather-start.6", "collective-permute-start.9"]


# -- comm_time's arithmetic ---------------------------------------------------


def test_comm_time_on_overlapping_intervals():
    table = profile.comm_table(TEXT)
    ops = [("fusion.1", 0.0, 2.0),
           ("all-gather-start.6", 2.0, 2.25),      # in flight 2.0 - 7.0
           ("fusion.2", 2.25, 4.0),
           ("async-collective-start", 4.0, 4.5),   # in flight 4.0 - 9.0
           ("fusion.8", 4.5, 6.0),                 # compute, carrying a step
           ("all-gather-done.6", 6.5, 7.0),
           ("fusion.5", 7.0, 8.0),                 # synchronous: alone
           ("async-collective-done", 8.5, 9.0),
           ("all-reduce.1", 10.0, 10.5),
           ("fusion.3", 10.5, 12.0)]
    # The profiler writes the pairs it knows on the asynchronous line; the
    # compiler's own form is joined from its halves.
    async_ops = [("all-gather-start.6", 2.0, 7.0)]
    out = profile.comm_time(ops, async_ops, table)
    assert out["comm_s"] == pytest.approx(7.0 + 0.5)
    assert out["by_kind"] == {"all-gather": pytest.approx(7.0),
                              "reduce-scatter": pytest.approx(1.0),
                              "all-reduce": pytest.approx(0.5)}
    assert out["by_scope"] == {"param_gather": pytest.approx(7.0),
                               "grad_sync": pytest.approx(1.0),
                               "loss_sync": pytest.approx(0.5)}
    # fusion.2 and fusion.8 hide 1.75 + 1.5 s of the union.
    assert out["exposed_s"] == pytest.approx(7.5 - 3.25)
    # Without the asynchronous line the first pair is whole all the same.
    assert profile.comm_time(ops, [], table) == out
    # A second run of the step pairs its own halves.
    again = ops + [(name, lo + 20.0, hi + 20.0) for name, lo, hi in ops]
    twice = profile.comm_time(again, [], table)
    assert twice["comm_s"] == pytest.approx(2 * out["comm_s"])
    assert twice["exposed_s"] == pytest.approx(2 * out["exposed_s"])
    assert profile.comm_time([], [], table) == {
        "comm_s": 0.0, "exposed_s": 0.0, "by_kind": {}, "by_scope": {}}
    assert profile.comm_time(ops, async_ops, {})["comm_s"] == 0.0


# -- the v5e's own text and trace ----------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED + ".step_text.txt.gz", "rt") as f:
        text = f.read()
    with gzip.open(RECORDED + ".events.json.gz", "rt") as f:
        events = json.load(f)

    def seconds(found):
        return [(name, lo * 1e-9, hi * 1e-9) for name, lo, hi in found]
    return text, seconds(events["ops"]), seconds(events["async"])


def _computations(text):
    """``{computation: its lines}`` of a compiled text."""
    found, name = {}, None
    for line in text.splitlines():
        header = profile._COMPUTATION_RE.match(line) \
            if line[:1] not in (" ", "\t") else None
        name = header.group(1) if header else name
        if name and not header:
            found.setdefault(name, []).append(line)
    return found


def _instructions(text):
    """``{instruction: its line}`` of a compiled text."""
    found = {}
    for line in text.splitlines():
        m = profile._INSTRUCTION_RE.match(line)
        if m:
            found[m.group(1)] = line
    return found


def test_the_v5es_fused_reduce_scatter_is_named(recorded):
    text, ops, _ = recorded
    table, scopes = profile.comm_table(text), profile.scope_table(text)
    computations = _computations(text)
    entry = _instructions(text)
    fused = {name for name, row in table.items() if name.startswith("fusion")}
    assert len(fused) == 51
    for name in fused:
        # The compiler's rewrite: a kCustom fusion over a pad, an all-reduce
        # and a dynamic-slice, and no op_name anywhere.
        called = profile._CALLS_RE.search(entry[name]).group(1)
        body = "\n".join(computations[called])
        assert called.startswith("all-reduce-scatter")
        assert " all-reduce(" in body and " dynamic-slice(" in body
        assert "op_name" not in entry[name] and "op_name" not in body
        assert table[name] == dict(table[name], kind="reduce-scatter",
                                   group=4, scope="grad_sync")
        assert table[name]["async"] is False
        assert scopes[name] == ("grad_sync", "update")
    # The payload is the padded array the all-reduce carries: fusion.203's
    # f32[416,1600] shard of a gradient f32[1600,1600] padded to 1,664 rows.
    assert table["fusion.203"]["bytes"] == 1664 * 1600 * 4
    # They are the events a trace names ``fusion.N`` and nothing else.
    assert fused <= {name for name, _, _ in ops}
    # What kept its name keeps its place: the one reduce-scatter the
    # compiler left alone is ``transpose(jvp(param_gather))``'s.
    plain = table["reduce_scatter.6175"]
    assert (plain["kind"], plain["scope"], plain["bytes"]) == (
        "reduce-scatter", "param_gather", 4 * 1600 * 1024)


def test_the_rule_on_the_v5es_text(recorded):
    text, _, _ = recorded
    table = profile.comm_table(text)
    tally = collections.Counter(
        (row["kind"], bool(row["async"]), row["scope"])
        for row in table.values())
    assert tally == {
        ("reduce-scatter", False, "grad_sync"): 51,
        ("reduce-scatter", False, "param_gather"): 1,
        # The combined all-reduces carry no name: the embedding's gradient
        # (f32[50257,1600], not divisible by four) and two tuples of the
        # small variables.
        ("all-reduce", False, "grad_sync"): 3,
        # Synchronous gathers of bf16[1600,1600] at the head of the step,
        ("all-gather", False, "param_gather"): 41,
        # and the compiler's own asynchronous form, eleven pairs.
        ("all-gather", True, "param_gather"): 22,
        # The padded rows moved back to the shards' true boundaries.
        ("collective-permute", True, UNATTRIBUTED): 40}
    assert table["all-reduce.482"]["bytes"] == 50257 * 1600 * 4
    start = table["async-collective-start.24"]
    assert start == {"kind": "all-gather", "bytes": 1600 * 6400 * 2,
                     "group": 4, "async": "async-collective-start.24",
                     "scope": "param_gather"}
    assert table["async-collective-done.24"] == dict(start, bytes=0)
    # The rule moved what had no scope and nothing else.
    before = profile._voted(*profile._parse_scopes(text)[:3])
    after = profile.scope_table(text)
    moved = {name for name in after if after[name] != before[name]}
    assert moved == {name for name, row in table.items()
                     if row["scope"] == "grad_sync"}
    assert all(before[name] == (UNATTRIBUTED, UNATTRIBUTED)
               for name in moved)
    # A matrix product that carries a step of a gather is compute.
    carried = [name for name, line in _instructions(text).items()
               if "calls=%async_collective_fusion" in line]
    assert carried and not set(carried) & set(table)
    assert {after[name][0] for name in carried} <= {"attn", "mlp"}


def test_comm_time_on_the_v5es_trace(recorded):
    text, ops, async_ops = recorded
    table = profile.comm_table(text)
    fused = {name: row for name, row in table.items()
             if name.startswith("fusion")}
    fused_s = sum(hi - lo for name, lo, hi in ops if name in fused)
    assert fused_s == pytest.approx(13.222228e-3)
    # Synchronous on the core, nothing beside it: exposed whole.
    alone = profile.comm_time(ops, async_ops, fused)
    assert alone["comm_s"] == pytest.approx(fused_s)
    assert alone["exposed_s"] == pytest.approx(fused_s)
    assert alone["by_scope"] == {"grad_sync": pytest.approx(fused_s)}
    out = profile.comm_time(ops, async_ops, table)
    assert out["comm_s"] == pytest.approx(31.078101e-3)
    assert out["exposed_s"] == pytest.approx(24.236608e-3)
    assert out["by_kind"] == {
        "all-gather": pytest.approx(11.213656e-3),
        "reduce-scatter": pytest.approx(13.297781e-3),
        "all-reduce": pytest.approx(5.986654e-3),
        "collective-permute": pytest.approx(0.580010e-3)}
    assert out["by_scope"]["grad_sync"] == pytest.approx(19.208882e-3)
    # Every synchronous row is exposed whole; of the pairs' 7.14 ms in flight
    # only what no matrix product covers is.
    synchronous = sum(hi - lo for name, lo, hi in ops
                      if name in table and not table[name]["async"])
    assert synchronous == pytest.approx(23.934900e-3)
    assert synchronous <= out["exposed_s"] <= out["comm_s"]
    # The profiler writes the permutes on the asynchronous line and not the
    # compiler's own gathers; without the line the halves give the same.
    assert {name.rsplit(".", 1)[0] for name, _, _ in async_ops
            if name in table} == {"collective-permute-start"}
    halves = profile.comm_time(ops, [], table)
    assert halves["comm_s"] == pytest.approx(out["comm_s"], rel=1e-6)
    assert halves["exposed_s"] == pytest.approx(out["exposed_s"], rel=1e-6)
