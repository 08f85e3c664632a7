"""Retune decision-shipping drill: the SAME script runs on every process.

ROADMAP item: prove the chief->worker verdict channel
(``autodist_tpu/retune/shipping.py``) over a LIVE coordination service,
not a dict-backed stub — the chief publishes a tier-1 exec-knob decision
under the process-global window sequence, the follower's
:class:`FollowerController` fetches it, validates the fingerprint echo
and the megastep boundary, and BOTH processes apply the switch at the
same boundary, then keep training under the new unroll.  The fleet never
splits: both processes end on unroll=2 and verify finite losses.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
_DEVS = os.environ.get("AUTODIST_TEST_DEVCOUNT", "4")
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_DEVS}"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import itertools  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import AutoDist  # noqa: E402
from autodist_tpu.retune import controller as controller_mod  # noqa: E402
from autodist_tpu.strategy import PS  # noqa: E402

BOUNDARY = 4  # the megastep boundary both sides must agree on


def loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def main():
    spec_file = sys.argv[1]
    out_path = sys.argv[2] if len(sys.argv) > 2 else None

    # Construct FIRST: "launch: local" spawns workers and joins the
    # coordination service before any code can initialize the backend.
    ad = AutoDist(resource_spec_file=spec_file, strategy_builder=PS())

    rng = np.random.RandomState(7)
    x = rng.randn(64, 8).astype(np.float32)
    y = rng.randn(64, 1).astype(np.float32)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch=(x, y))
    runner = ad.create_distributed_session(item)
    state = runner.create_state()

    pid = jax.process_index()
    per = 64 // jax.process_count()
    local = (x[pid * per:(pid + 1) * per], y[pid * per:(pid + 1) * per])
    for _ in range(2):  # warm the incumbent before the switch window
        state, metrics = runner.step(state, local)

    # The resolver must hand the chief a publishing Controller and the
    # worker a FollowerController — both over the LIVE coordination
    # service KV channel (a None here means the channel is missing and
    # multi-process retuning was declined; that is the bug this drill
    # exists to catch).
    ctl = controller_mod.controller_for(runner, unroll=1)
    assert ctl is not None, \
        "controller_for declined: no KV byte channel on a live 2-process job"
    assert ctl._channel is not None

    if pid == 0:
        assert not isinstance(ctl, controller_mod.FollowerController)
        decision = controller_mod.Decision(
            tier=1, label="exec:unroll=2",
            knobs={"unroll": 2, "bucket_mb": 0, "microbatches": 0},
            strategy=None, strategy_name="",
            predicted_ms=1.0, incumbent_predicted_ms=2.0, measured_ms=2.0,
            margin_pct=50.0, remaining_steps=100)
        # Publish the canonical verdict blob + fingerprint echo under the
        # process-global window sequence — exactly what
        # Controller.observe_window does after a qualifying evaluation.
        seq, fp = ctl._channel.publish(decision, boundary=BOUNDARY)
        assert seq == 1 and len(fp) == 16
    else:
        assert isinstance(ctl, controller_mod.FollowerController)
        # The follower's window: fetch + fingerprint echo + boundary
        # check + materialize — ShipMismatch (loud, fleet-preserving)
        # on any disagreement.
        decision = ctl.observe_window(2.0, remaining_steps=100,
                                      step=BOUNDARY)
        assert decision is not None, "follower fetched a hold verdict"
        assert decision.tier == 1 and decision.knobs["unroll"] == 2, decision

    # BOTH processes switch at the same megastep boundary.
    state, new_unroll = ctl.apply(state, decision, step=BOUNDARY)
    assert new_unroll == 2, f"switch did not land: unroll={new_unroll}"

    # Keep training under the new knobs: 2 megasteps of 2 — the re-lowered
    # megastep program crosses the process boundary like any other step.
    state, metrics = runner.run(state, itertools.repeat(local), 4,
                                unroll=new_unroll)
    loss = float(np.ravel(jax.device_get(metrics["loss"]))[-1])
    assert np.isfinite(loss), f"post-switch loss not finite: {loss}"

    print(f"RETUNE_SHIP_OK process={pid} unroll={new_unroll} "
          f"loss={loss:.6f}", flush=True)
    if out_path:
        with open(f"{out_path}.p{pid}", "w") as f:
            f.write(f"OK unroll={new_unroll}")
    # No explicit join: jax.distributed's atexit shutdown is a cross-process
    # barrier (see worker_script.py).


if __name__ == "__main__":
    main()
