"""The reduction of a device trace: on hand-built intervals whose answers
are known, and pinned on a trace recorded on the chip."""
import pathlib

import pytest

from chipbench import flops, trace_reduce as tr

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_total_clip_intersect_gaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2), (5, 5)]) == [
        (0, 2), (3, 4)]
    assert tr.total([(0, 2), (3, 4)]) == 3
    assert tr.clip([(0, 2), (3, 4), (6, 7)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.intersect([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == [
        (1, 2), (3, 4), (5, 6)]
    assert tr.intersect([(0, 1)], []) == []
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([(0, 5)], 0, 5) == []
    assert tr.gaps([], 1, 2) == [(1, 2)]


def test_names():
    text = "%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p.1)"
    assert tr.op_name(text) == "fusion.12"
    assert tr.op_label(text) == "fusion bf16[8,128]"
    assert tr.op_label("%f.3 = (f32[4]{0:T(4)S(1)}, bf16[2,2]{1,0}) fusion(%a)") \
        == "f (f32[4], bf16[2,2])"
    assert tr.op_label("copy.7") == "copy"
    assert len(tr.op_label("%x.1 = (" + "f32[1024]{0}, " * 40 + ") y()")) == 80
    assert tr.op_name("flash_fwd.7") == "flash_fwd.7"
    assert tr.op_group("fusion.12") == "fusion"
    assert tr.op_group("all-gather-start.3.1") == "all-gather-start"
    assert tr.op_group("flash_bwd_dkv") == "flash_bwd_dkv"
    # The opcode where the event has the instruction's text, whatever its
    # name and whatever it reads; the name's group where it has not.
    assert tr.op_kind(text) == "fusion"
    assert tr.op_kind("%loop_body.3 = (s32[], f32[8]{0:T(8)S(1)}) while(%t), "
                      "condition=%c, body=%b") == "while"
    assert tr.op_kind("%fusion.5 = f32[8]{0} fusion(%while.3), "
                      "calls=%conditional_branch") == "fusion"
    assert tr.op_kind("conditional.4") == "conditional"
    assert tr.op_kind("%all-gather-start.1 = ...") == "all-gather-start"


def _hand_built():
    """Two chips, one program of 10 s each after two skipped ones.

    Chip 0: a matmul 10-13, an asynchronous all-gather in flight 12-16
    (start 12-12.5, done 15-16, the whole on the line of asynchronous
    operations) with a kernel 13-14 under it, idle 14-15 and 16-17, a
    synchronous all-reduce 17-19, idle 19-20.  The all-gather's start lies
    inside the matmul's span, and the matmul is no loop: it keeps its 3 s.
    Chip 1: busy 10-20 with one fusion, no collectives.
    Names are whole instructions, as the chip's trace gives them.
    """
    modules = [("jit_step", 0, 4), ("jit_step", 5, 9), ("jit_step", 10, 20)]
    matmul = "%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p.1, %p.2)"
    gather = ("%all-gather-start.2 = (f32[4]{0}, f32[16]{0}) "
              "all-gather-start(%p.3)")
    chip0 = [(matmul, 0, 4), (matmul, 5, 9), (matmul, 10, 13),
             (gather, 12, 12.5),
             ("%flash_fwd.3 = (bf16[4,128,64]{2,1,0}, f32[4,128,1]{2,1,0}) "
              "custom-call(%p.4)", 13, 14),
             ("%all-gather-done.2 = f32[16]{0} all-gather-done(%x)", 15, 16),
             ("%all-reduce.4 = f32[16]{0} all-reduce(%y)", 17, 19)]
    chip1 = [("fusion.9", 0, 4), ("fusion.9", 5, 9), ("fusion.9", 10, 20)]
    host = [("chipbench.dispatch", 14.2, 14.9), ("chipbench.block", 15.5, 30),
            ("chipbench.next_batch", 14.0, 14.1)]
    return {"chips": {0: {"ops": chip0, "modules": modules,
                          "async": [(gather, 12, 16),
                                    ("%copy-start.8 = ...", 10, 20)]},
                      1: {"ops": chip1, "modules": modules, "async": []}},
            "host": host}


def test_reduce_on_hand_built_intervals():
    out = tr.reduce(_hand_built(), kernels=flops.KERNELS, skip_programs=2)
    assert out["chips"] == 2 and out["window_s"] == 10
    # chip 0 busy: 10-14, 15-16, 17-19 = 7; chip 1: 10.
    assert out["busy_s"] == pytest.approx(8.5)
    assert out["idle_share"] == pytest.approx(0.15)
    # chip 0 collectives: 12-16 and 17-19 = 6.  Chip 1 has no line of
    # asynchronous operations, so the time in flight is chip 0's.
    assert out["collective_s"] == pytest.approx(6.0)
    # Of those 6 s, 12-14 run under the matmul and the kernel: 4 exposed.
    assert out["collective_exposed_s"] == pytest.approx(2.0)
    assert out["kernel_seconds"] == {"flash_fwd": pytest.approx(0.5)}
    assert out["kernel_calls"] == {"flash_fwd": pytest.approx(0.5)}
    ops = dict(out["op_seconds"])
    assert ops["fusion bf16[8,128]"] == pytest.approx(3 / 2)
    assert ops["fusion"] == pytest.approx(10 / 2)
    assert ops["all-reduce f32[16]"] == pytest.approx(1.0)
    assert ops["all-gather-done f32[16]"] == pytest.approx(0.5)
    assert ops["flash_fwd (bf16[4,128,64], f32[4,128,1])"] \
        == pytest.approx(0.5)
    # Gaps of chip 0: 14-15 (dispatch covers 0.7 of it), 16-17 and 19-20
    # (inside the block).
    assert out["longest_gaps"] == [
        ("chipbench.dispatch", pytest.approx(1.0)),
        ("chipbench.block", pytest.approx(1.0)),
        ("chipbench.block", pytest.approx(1.0))]
    assert dict(out["gap_seconds_by_owner"]) == {
        "chipbench.block": pytest.approx(1.0),
        "chipbench.dispatch": pytest.approx(0.5)}


def _line_with_a_loop():
    """One chip's line: a fusion 0-2, a ``while`` 2-9 whose body runs
    twice (a kernel 2-4 and 5.5-7.5, a fusion 4-5 and 7.5-8.5, so that the
    loop itself costs 5-5.5 and 8.5-9), inside it a ``conditional`` 4-5
    around the first of those fusions, a neighbour 9-10, an event of no
    length inside the neighbour."""
    loop = ("%while.5 = (s32[], f32[4096,3072]{1,0}) while(%tuple.1), "
            "condition=%cond, body=%body")
    kernel = "%jvp_jit_gmm_.3 = bf16[2560,1024]{1,0} custom-call(%a, %b)"
    return [("fusion.1", 0, 2), (loop, 2, 9), (kernel, 2, 4),
            ("%conditional.2 = f32[8]{0} conditional(%p)", 4, 5),
            ("fusion.7", 4, 5), (kernel, 5.5, 7.5), ("fusion.7", 7.5, 8.5),
            ("fusion.9", 9, 10), ("custom-call.4", 9.5, 9.5)]


def test_leaves_drops_a_loop_that_contains_others_of_its_line():
    line = _line_with_a_loop()
    kept = tr.leaves(line)
    assert [tr.op_group(tr.op_name(n)) for n, _, _ in kept] == [
        "fusion", "jvp_jit_gmm_", "fusion", "jvp_jit_gmm_", "fusion",
        "fusion", "custom-call"]
    assert sum(b - a for _, a, b in kept) == 9
    assert sum(b - a for _, a, b in line) == 17
    # Events that only touch, or overlap without one holding the other,
    # are nobody's body.
    line = [("while.1", 0, 1), ("b", 1, 2), ("while.2", 1.5, 3), ("c", 2, 4)]
    assert tr.leaves(line) == line
    assert tr.leaves([]) == []
    assert tr.leaves([("while.3", 0, 4), ("b", 3, 4), ("c", 5, 5)]) == [
        ("b", 3, 4), ("c", 5, 5)]
    # Only a loop or a conditional is a container: a fusion with an
    # asynchronous start inside its span is work, and keeps its length.
    line = [("fusion.1", 0, 4), ("all-gather-start.2", 3, 3.5),
            ("%copy.3 = f32[8]{0} copy(%while.3)", 5, 7), ("d", 6, 7)]
    assert tr.leaves(line) == line


def test_reduce_sums_the_body_and_not_the_loop_around_it():
    modules = [("jit_step", 0, 10)]
    trace = {"chips": {0: {"ops": _line_with_a_loop(), "modules": modules,
                           "async": []}}, "host": []}
    out = tr.reduce(trace, kernels=("gmm",), skip_programs=0)
    # Busy time is the union of every event, the loop's own cost between
    # its body's operations with it: no idle gap opens at 5-5.5 or 8.5-9.
    assert out["busy_s"] == pytest.approx(10) and out["window_s"] == 10
    assert out["idle_share"] == pytest.approx(0.0)
    assert out["longest_gaps"] == []
    ops = dict(out["op_seconds"])
    assert not [name for name in ops if name.startswith(("while",
                                                         "conditional"))]
    assert ops == {"fusion": pytest.approx(5.0),
                   "jvp_jit_gmm_ bf16[2560,1024]": pytest.approx(4.0)}
    assert sum(ops.values()) == pytest.approx(9.0)
    assert out["kernel_seconds"] == {"gmm": pytest.approx(4.0)}
    assert out["kernel_calls"] == {"gmm": pytest.approx(2)}


def test_collective_intervals_take_both_lines():
    chip = {"ops": [("%all-gather-start.1 = ...", 0, 1), ("fusion.5", 2, 3),
                    ("%all-gather-done.1 = ...", 3, 4),
                    ("reduce-scatter.7", 6, 7), ("copy-start.2", 7, 8)],
            "async": [("%all-gather-start.1 = ...", 0, 4),
                      ("%copy-start.2 = ...", 7, 9)]}
    assert tr.collective_intervals(chip) == [
        ("all-gather-start.1", 0, 1), ("all-gather-start.1", 0, 4),
        ("all-gather-done.1", 3, 4), ("reduce-scatter.7", 6, 7)]
    assert tr.collective_intervals({"ops": [("fusion.1", 0, 1)]}) == []


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce({"chips": {}, "host": []})
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce({"chips": {0: {"ops": [], "modules": []}}, "host": []})


def test_without_a_line_of_programs_the_slice_is_the_operations_span():
    trace = {"chips": {0: {"ops": [("fusion.1", 1, 2), ("fusion.2", 3, 4)],
                           "modules": []}}, "host": []}
    out = tr.reduce(trace)
    assert out["window_s"] == 3 and out["busy_s"] == 2
    assert out["longest_gaps"] == [("no annotation", 1)]


# -- pinned on traces recorded on the chip -----------------------------------
# Three programs (steps) of a traced run, trimmed to the lines the
# reduction reads (scratch tool, PERF.md section 6); the first two programs
# are skipped, as in a run.


def test_recorded_one_chip_trace_gpt2_medium():
    """``gpt2-medium.train-s1024`` on one v5e chip (my chip run, PR 22)."""
    trace = tr.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    assert sorted(trace["chips"]) == [0]
    assert len(trace["chips"][0]["modules"]) == 3
    assert {name for name, _, _ in trace["host"]} == {
        "chipbench.next_batch", "chipbench.dispatch", "chipbench.block"}
    out = tr.reduce(trace, kernels=flops.KERNELS)
    assert out["chips"] == 1 and out["programs"] == 1
    # One step of 192.8 ms, the chip busy for all but 58 us of it.
    assert out["window_s"] == pytest.approx(0.192836053, abs=1e-9)
    assert out["busy_s"] == pytest.approx(0.192778431, abs=1e-9)
    assert out["idle_share"] == pytest.approx(2.988e-4, rel=1e-3)
    assert out["collective_s"] == 0 and out["collective_exposed_s"] == 0
    # 24 layers: one call of each kernel a layer, named as the program
    # named them, whatever jvp and transpose wrapped around them.
    assert out["kernel_calls"] == {k: 24 for k in flops.KERNELS}
    assert out["kernel_seconds"] == {
        "flash_fwd": pytest.approx(0.015215408, abs=1e-9),
        "flash_bwd_dq": pytest.approx(0.015878854, abs=1e-9),
        "flash_bwd_dkv": pytest.approx(0.020664606, abs=1e-9)}
    label, seconds = out["op_seconds"][0]
    assert label == "flash_bwd_dkv (f32[128,1024,64], f32[128,1024,64])"
    assert seconds == pytest.approx(0.020664606, abs=1e-9)
    assert len(out["op_seconds"]) == 10
    # The gaps are the 39 us between two programs and slivers inside one;
    # the host was waiting in its lagged block.
    assert out["longest_gaps"][0] == (
        "chipbench.block", pytest.approx(3.8743e-05, rel=1e-3))
    assert sum(s for _, s in out["gap_seconds_by_owner"]) \
        == pytest.approx(out["window_s"] - out["busy_s"], abs=1e-12)
    # Two programs when only one is skipped.
    two = tr.reduce(trace, kernels=flops.KERNELS, skip_programs=1)
    assert two["programs"] == 2
    assert two["window_s"] == pytest.approx(0.385675961, abs=1e-9)
    assert two["kernel_calls"]["flash_fwd"] == 48


def test_recorded_roofline_from_the_pinned_kernel_times():
    """The reader's arithmetic on the recorded slice: 8 rows x 16 heads,
    causal, at the v5e's peaks gives 18.2% of the roofline."""
    from chipbench.catalog import Catalog
    catalog = Catalog(str(ROOT))
    cell = catalog.cell("gpt2-medium.train-s1024")
    kind = catalog.module("kinds", "causal_lm")
    reader = catalog.module("layer_metrics", "attn_kernel_roofline")
    out = tr.reduce(tr.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz"),
                    kernels=flops.KERNELS)
    run = {"trace": out, "peak": catalog.peak("TPU v5 lite"),
           "attention": kind.attention_calls(cell["sizes"], cell["mix"])}
    # least time: 24 x (87.2 + 130.8 + 174.4 us) = 9.42 ms of 51.76 ms
    assert reader.read(run) == pytest.approx(18.20, abs=0.01)
    share = catalog.module("layer_metrics", "attn_kernel_share")
    assert share.read(run) == pytest.approx(26.85, abs=0.01)


def test_recorded_four_chip_trace_gpt2_xl():
    """``gpt2-xl.train-s1024-x4`` (my chip run, PR 22): chips 0 and 1 of the
    four, three programs.  The partitioner turned most gathers into
    asynchronous ``collective-permute``s; the line of asynchronous
    operations exists on chip 0 only."""
    trace = tr.load(DATA / "gpt2-xl.train-s1024-x4.xplane.pb.gz")
    assert sorted(trace["chips"]) == [0, 1]
    assert len(trace["chips"][0]["async"]) == 864
    assert trace["chips"][1]["async"] == []
    kinds = {tr.op_group(name)
             for name, _, _ in tr.collective_intervals(trace["chips"][0])}
    assert kinds == {"collective-permute-start", "collective-permute-done",
                     "all-gather", "all-reduce"}
    out = tr.reduce(trace, kernels=flops.KERNELS)
    assert out["chips"] == 2 and out["programs"] == 1
    assert out["window_s"] == pytest.approx(0.30225311, abs=1e-8)
    assert out["busy_s"] == pytest.approx(0.302059173, abs=1e-8)
    # In flight for 25.1% of the step (chip 0, which has the line); with no
    # other operation on the chip for 6.5% of it, on either chip.
    assert out["collective_s"] == pytest.approx(0.075785713, abs=1e-8)
    assert out["collective_exposed_s"] == pytest.approx(0.019596176,
                                                        abs=1e-8)
    assert out["kernel_calls"] == {k: 48 for k in flops.KERNELS}
    assert out["kernel_seconds"]["flash_bwd_dkv"] == pytest.approx(
        0.016053425, abs=1e-8)
    assert out["op_seconds"][0][0] == "fusion f32[416,1600]"
    assert out["longest_gaps"][0][0] == "chipbench.block"
    for chip, in_flight in ((0, 0.075785713), (1, 0.019557688)):
        one = tr.reduce({"chips": {chip: trace["chips"][chip]},
                         "host": trace["host"]})
        assert one["collective_s"] == pytest.approx(in_flight, abs=1e-8)
        assert one["collective_exposed_s"] == pytest.approx(0.0196, abs=1e-4)
