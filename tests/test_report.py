"""Transform report: one command -> one HTML page with all four stages.

Parity target: the reference's per-stage TensorBoard snapshots
(``/root/reference/autodist/kernel/graph_transformer.py:62-90``,
``utils/visualization_util.py:24-36``) — here a self-contained HTML file
rendered by the chief on every compile, upgradable with the compiled-HLO
collective summary.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu import AutoDist, const
from autodist_tpu.strategy import PS


@pytest.fixture(autouse=True)
def _graph_dump_dir_of_its_own(monkeypatch, tmp_path):
    """``report.html`` mirrors the newest compile of every process that
    shares the working directory, so under ``-n 6`` a neighbour's compile
    replaces or removes the file a test here is about to read.  Each test
    renders into a directory no other process knows."""
    monkeypatch.setattr(const, "DEFAULT_GRAPH_DUMP_DIR",
                        str(tmp_path / "graphs"))


def _build():
    def loss_fn(params, batch):
        x, y = batch
        h = jax.nn.relu(x @ params["w1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    rng = np.random.RandomState(0)
    params = {"w1": jnp.zeros((16, 32)), "w2": jnp.zeros((32, 4))}
    batch = (rng.randn(16, 16).astype(np.float32),
             rng.randn(16, 4).astype(np.float32))
    ad = AutoDist(strategy_builder=PS())
    item = ad.capture(loss_fn, params, optax.adam(1e-3), example_batch=batch)
    runner = ad.create_distributed_session(item)
    return runner, batch


def test_report_auto_rendered_on_compile():
    runner, batch = _build()
    path = os.path.join(const.DEFAULT_GRAPH_DUMP_DIR, "report.html")
    assert not os.path.exists(path)
    state = runner.create_state()
    runner.step(state, batch)  # first compile triggers the chief's report
    assert os.path.exists(path), "report.html not auto-rendered on compile"
    text = open(path).read()
    assert "<code>w1</code>" in text and "<code>w2</code>" in text
    assert "PS dest=" in text            # strategy column
    assert "explicit (shard_map)" in text or "GSPMD (jit)" in text
    assert "storage sharding" in text


def test_report_with_hlo_collective_summary():
    runner, batch = _build()
    state = runner.create_state()
    state, _ = runner.step(state, batch)
    path = runner.write_report(batch)
    text = open(path).read()
    # PS => ZeRO-1 lowering: the compiled step's collectives must show up.
    assert "reduce-scatter" in text and "all-gather" in text
    assert "Compiled step (HLO)" in text


def test_report_written_per_strategy_with_stable_alias_and_history():
    """Reports are keyed by strategy id (history survives recompiles);
    report.html mirrors the newest; the footer links prior reports."""
    runner, batch = _build()
    state = runner.create_state()
    runner.step(state, batch)
    sid = runner.program.strategy.id
    per_id = os.path.join(const.DEFAULT_GRAPH_DUMP_DIR,
                          f"report_{sid}.html")
    stable = os.path.join(const.DEFAULT_GRAPH_DUMP_DIR, "report.html")
    assert os.path.exists(per_id), "per-strategy-id report missing"
    assert os.path.exists(stable), "stable report.html alias missing"
    assert open(per_id).read() == open(stable).read()

    # A second program (new strategy id) must not clobber the first's
    # page, must retarget the alias, and must link back to the first.
    from autodist_tpu.autodist import _reset_default
    _reset_default()
    runner2, batch2 = _build()
    state2 = runner2.create_state()
    runner2.step(state2, batch2)
    sid2 = runner2.program.strategy.id
    assert sid2 != sid
    per_id2 = os.path.join(const.DEFAULT_GRAPH_DUMP_DIR,
                           f"report_{sid2}.html")
    assert os.path.exists(per_id) and os.path.exists(per_id2)
    stable_text = open(stable).read()
    assert sid2 in stable_text
    assert f"report_{sid}.html" in open(per_id2).read(), \
        "footer must link the prior strategy's report"


# -- collective_summary / replica_group_sizes edge cases ---------------------
# These regexes back the HLO assertions of tests/test_topology_aot.py and
# tests/test_moe_hlo.py: an HLO form they silently stop matching empties
# an assertion, so every form XLA emits is pinned here.


def test_collective_summary_counts_plain_and_suffixed_invocations():
    from autodist_tpu.report import collective_summary
    hlo = """
  %ar = f32[4] all-reduce(f32[4] %x), replica_groups={{0,1}}, to_apply=%add
  %ar2 = f32[4] all-reduce.7(f32[4] %y), replica_groups={{0,1}}, to_apply=%add
  %ag = f32[8] all-gather(f32[4] %z), dimensions={0}
"""
    counts = collective_summary(hlo)
    assert counts["all-reduce"] == 2  # plain + .N-suffixed
    assert counts["all-gather"] == 1
    assert "reduce-scatter" not in counts  # zero -> omitted by default
    assert collective_summary(hlo, keep_zeros=True)["reduce-scatter"] == 0


def test_collective_summary_async_pairs_count_once():
    """Async collectives appear as a -start/-done pair: the -start is the
    invocation; counting -done too would double every async op."""
    from autodist_tpu.report import collective_summary
    hlo = """
  %ars = f32[4] all-reduce-start(f32[4] %x), to_apply=%add
  %ard = f32[4] all-reduce-done(f32[4] %ars)
  %rss = f32[2] reduce-scatter-start.3(f32[4] %y), to_apply=%add
  %rsd = f32[2] reduce-scatter-done.3(f32[2] %rss)
"""
    counts = collective_summary(hlo)
    assert counts["all-reduce"] == 1
    assert counts["reduce-scatter"] == 1


def test_collective_summary_sees_ops_inside_fusions():
    """A .N-suffixed invocation nested in a fusion body must count; the
    op's own result name (%all-reduce.3 = ...) must not double-count."""
    from autodist_tpu.report import collective_summary
    hlo = """
%fused_computation.1 {
  %p0 = f32[4] parameter(0)
  %all-reduce.3 = f32[4] all-reduce(f32[4] %p0), to_apply=%add
  ROOT %r = f32[4] add(f32[4] %all-reduce.3, f32[4] %p0)
}
"""
    # One invocation: the .N-suffixed *instruction name* occurrences
    # (definition lhs + operand references) must not inflate the count.
    assert collective_summary(hlo)["all-reduce"] == 1
    # Suffixed *opcode* form (StableHLO-ish dumps): still one invocation.
    assert collective_summary(
        "  %x = f32[4] all-reduce.9(f32[4] %p0)")["all-reduce"] == 1


def test_collective_summary_does_not_cross_match_op_names():
    """'all-reduce' must not match inside 'reduce-scatter' or vice versa,
    and 'all-gather' must not match 'all-gather-done'."""
    from autodist_tpu.report import collective_summary
    hlo = """
  %rs = f32[2] reduce-scatter(f32[4] %x), to_apply=%add
  %agd = f32[8] all-gather-done(f32[8] %h)
"""
    counts = collective_summary(hlo, keep_zeros=True)
    assert counts["reduce-scatter"] == 1
    assert counts["all-reduce"] == 0
    assert counts["all-gather"] == 0


def test_replica_group_sizes_parses_both_hlo_syntaxes():
    """XLA emits replica groups either as iota form [G,S]<=[...] or as the
    explicit brace form {{0,1},{2,3}}; a pass/version switching form must
    not silently empty the set (the detached-topology cases read it)."""
    from autodist_tpu.report import replica_group_sizes
    iota = "all-reduce(a), replica_groups=[4,2]<=[8], to_apply=add"
    brace = "all-reduce(a), replica_groups={{0,1,2,3},{4,5,6,7}}"
    assert replica_group_sizes(iota) == {2}
    assert replica_group_sizes(brace) == {4}
    assert replica_group_sizes(iota + "\n" + brace) == {2, 4}
    assert replica_group_sizes("no collectives here") == set()
    # Non-uniform brace groups (XLA permits them): every size must appear.
    uneven = "all-reduce(a), replica_groups={{0},{1,2,3}}"
    assert replica_group_sizes(uneven) == {1, 3}
