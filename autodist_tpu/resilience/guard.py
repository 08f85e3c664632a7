"""StepGuard: NaN/Inf divergence detection with checkpoint rollback.

A NaN loss on one replica poisons every replica's donated state within a
step (the gradient all-reduce spreads it), and the periodic checkpointer
would then happily persist the poisoned state.  The guard closes both
holes:

* the Runner's compiled step computes a **device-side** ``notfinite``
  flag (one fused scalar op; no host sync), and the guard transfers it
  only every ``check_every`` steps — and always right before a
  checkpoint save, so no poisoned state is ever persisted;
* on divergence it **rolls back** to the last good state (the bound
  CheckpointManager's latest step, or an in-memory device snapshot when
  running without checkpoints), skips ahead in the data stream (the
  presumed-bad batch is consumed and not replayed), and counts a strike;
* ``max_strikes`` consecutive rollbacks without progress raise
  :class:`DivergenceAbort` — persistent divergence is a bug, not a blip.
"""
import jax
import numpy as np

from autodist_tpu import const
from autodist_tpu.utils import logging

#: Consecutive rollbacks a guard tolerates where none is passed.
MAX_STRIKES = 3


class DivergenceAbort(RuntimeError):
    """Raised when rollback+retry exhausted ``max_strikes``."""


class StepGuard:
    """Policy + state for the guarded step loop.

    Args:
        check_every: host-check cadence in steps (typed ENV default
            ``AUTODIST_GUARD_CHECK_EVERY``).  The device flag exists every
            step; only the host *transfer* is amortized.  NaN propagates
            through the params, so a divergence between checks is still
            caught at the next one.  Under ``Runner.run(unroll=K)`` the
            effective cadence rounds UP to a multiple of K (checks happen
            at megastep boundaries) and a rollback restores the
            megastep-ENTRY snapshot — the whole offending K-block is
            skipped, preserving the skip-offending-batches contract at
            megastep granularity.
        max_strikes: consecutive rollbacks tolerated before
            :class:`DivergenceAbort`.
        on_rollback: optional callback ``(step, strikes) -> None`` —
            the re-seeding hook (shuffle the data pipeline, bump an rng
            epoch) invoked after state is restored.
    """

    def __init__(self, check_every=None, max_strikes=MAX_STRIKES,
                 on_rollback=None):
        if check_every is None:
            check_every = const.ENV.AUTODIST_GUARD_CHECK_EVERY.val
        self.check_every = max(1, int(check_every))
        self.max_strikes = max(1, int(max_strikes))
        self.on_rollback = on_rollback
        self.strikes = 0
        self.rollbacks = 0          # lifetime count (reporting)
        self._snapshot = None       # (step, state) when no manager bound

    # -- detection -----------------------------------------------------------

    def due(self, step):
        """Whether the host-side flag check is due at ``step`` (1-based)."""
        return step % self.check_every == 0

    @staticmethod
    def diverged(metrics):
        """Host-check the device-side flag (one scalar transfer).

        Under fused multi-step dispatch (``Runner.run(unroll=K)``) the
        flag arrives pre-aggregated over the megastep's K steps (a
        device-side ``any``); a stacked per-step flag is also accepted
        (``np.any`` on the host side) so custom loops keep working.
        """
        flag = (metrics or {}).get("notfinite")
        if flag is None:
            return False
        return bool(np.any(jax.device_get(flag)))

    # -- last-good state tracking --------------------------------------------

    def mark_good(self, step, state, runner=None):
        """Record a healthy state as the in-memory rollback target.

        Only used when no CheckpointManager backs the loop (``Runner.run``
        with a guard): the state is copied on device — buffer donation
        would otherwise delete it on the next step.
        """
        copy = jax.tree_util.tree_map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x, state)
        self._snapshot = (step, copy)
        self.strikes = 0

    def progressed(self):
        """A healthy check after a rollback clears the strike counter."""
        self.strikes = 0

    # -- recovery ------------------------------------------------------------

    def rollback(self, step, manager=None):
        """Restore the last good state; returns ``(good_step, state)``.

        Raises :class:`DivergenceAbort` once ``max_strikes`` consecutive
        rollbacks have not produced a healthy check.
        """
        from autodist_tpu import resilience
        self.strikes += 1
        self.rollbacks += 1
        if self.strikes > self.max_strikes:
            resilience.record_event(
                "divergence-abort",
                f"step {step}: {self.strikes - 1} consecutive rollbacks "
                f"exhausted max_strikes={self.max_strikes}")
            raise DivergenceAbort(
                f"autodist_tpu: loss diverged at step {step} and "
                f"{self.strikes - 1} rollbacks did not recover "
                f"(max_strikes={self.max_strikes}); aborting. Check the "
                f"learning rate / data pipeline.")
        if manager is not None:
            state = manager.restore_or_init()
            # The restored state says which step actually survived —
            # restore_or_init may have fallen back past latest_step()
            # (corrupt newest step) or to fresh init (step 0).
            leaves = jax.tree_util.tree_leaves(getattr(state, "step", 0))
            good = int(jax.device_get(leaves[0])) if leaves else 0
        elif self._snapshot is not None:
            good, snap = self._snapshot
            # Re-copy: the restored state will be donated into the next
            # step, and the snapshot must survive for another rollback.
            state = jax.tree_util.tree_map(
                lambda x: x.copy() if isinstance(x, jax.Array) else x, snap)
        else:
            raise DivergenceAbort(
                "autodist_tpu: loss diverged and no rollback target exists "
                "(no CheckpointManager bound and no snapshot marked)")
        resilience.record_event(
            "rollback", f"divergence at step {step}: restored step {good} "
                        f"(strike {self.strikes}/{self.max_strikes})")
        logging.warning("step guard: non-finite loss at step %d — rolled "
                        "back to step %d (strike %d/%d)", step, good,
                        self.strikes, self.max_strikes)
        if self.on_rollback is not None:
            self.on_rollback(good, self.strikes)
        return good, state
