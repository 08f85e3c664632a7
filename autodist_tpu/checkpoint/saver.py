"""Saver: logical-name-keyed, sharding-agnostic checkpointing.

Parity: ``/root/reference/autodist/checkpoint/saver.py:27-133`` — the
reference subclasses ``tf.train.Saver`` so that (a) checkpoints are keyed by
the original single-node variable names even after the Partitioner split them
(``partitioner.py:292-347`` rebuilds SaveSliceInfo for this), and (b) vanilla
TF can read the result.

TPU equivalents here (orbax-backed):

* Keying: the checkpoint stores the *logical* params/state pytree — variable
  names are pytree paths, identical however the mesh shards them. No
  SaveSliceInfo surgery: a sharded ``jax.Array`` saves as one logical array.
* Resharding: restore takes the *current* runner's sharding plan, so a
  checkpoint written on one mesh (say 8-way PS-sharded) restores onto any
  other (say 2x4 data x model) — the reference's "single-node compatible"
  contract, generalized.
* Vanilla readability: ``Saver.restore_raw`` reads a checkpoint to host numpy
  with no framework objects, the analog of restoring with a vanilla
  ``tf.train.Saver`` (``tests/integration/cases/c0.py:128-136``).

Multi-host: orbax coordinates distributed writes internally (each process
writes its shards); paths must be on a filesystem all hosts see.
"""
import os
import time

import jax
import numpy as np
import orbax.checkpoint as ocp

from autodist_tpu import const, observability
from autodist_tpu.checkpoint import manifest as manifest_mod
from autodist_tpu.checkpoint.manifest import ManifestMismatchError
from autodist_tpu.graph_item import path_to_name
from autodist_tpu.resilience.retry import retry_call, transient_runtime_error
from autodist_tpu.runner import TrainState
from autodist_tpu.utils import logging


def _prune_sync_state(state):
    """Drop leafless sync-state subtrees (e.g. NoneCompressor's ``()``):
    they carry no data and would make checkpoints path-specific — a
    PartitionedPS (explicit-path) checkpoint must restore under an
    AllReduce (GSPMD) runner and vice versa."""
    return state._replace(sync_state={
        k: v for k, v in state.sync_state.items()
        if jax.tree_util.tree_leaves(v)})


def _shapes_match(restored, skel):
    """Leaf-for-leaf shape equality between a restored sync subtree and
    the live skeleton (structure mismatch counts as no)."""
    a = jax.tree_util.tree_leaves(restored)
    b = jax.tree_util.tree_leaves(skel)
    if len(a) != len(b):
        return False
    return all(tuple(np.shape(x)) == tuple(getattr(y, "shape", np.shape(y)))
               for x, y in zip(a, b))


def _rebuild_sync_state(runner, state):
    """Re-attach the runner's canonical sync-state structure after restore
    (leafless entries rebuilt structurally; missing compressor state — e.g.
    restoring a GSPMD checkpoint under an EF strategy — reinitialized).

    Cross-shape contract: sync state carries a leading device axis
    ``(n,) + unit_shape``, so state saved at a different world size has
    the wrong leading dim for this mesh — per-device error-feedback
    residuals are meaningless on a different device set anyway, so a
    shape-mismatched entry reinitializes fresh (recorded; the compressor
    re-accumulates its residual within a few steps)."""
    skel = jax.eval_shape(runner.create_state).sync_state
    restored = state.sync_state if isinstance(state.sync_state, dict) else {}
    out = {}
    for k, v in skel.items():
        if jax.tree_util.tree_leaves(v):
            if k in restored and jax.tree_util.tree_leaves(restored[k]):
                if _shapes_match(restored[k], v):
                    out[k] = restored[k]
                else:
                    logging.warning(
                        "compressor state for %s was saved at a different "
                        "world size; reinitializing", k)
                    out[k] = runner.fresh_sync_state(k)
            else:
                logging.warning("checkpoint has no compressor state for %s; "
                                "reinitializing", k)
                out[k] = runner.fresh_sync_state(k)
        else:
            out[k] = v  # structure only (no arrays), e.g. ()
    return state._replace(sync_state=out)


def reshard_state(runner, raw, saved_data_axis=None):
    """Rebuild a live TrainState on the *current* mesh from a raw
    (target-free, host) restore of a checkpoint written under a
    different topology — the cross-shape half of the elastic contract
    (docs/elasticity.md).

    Leaves are matched by normalized pytree path, not container type, so
    the raw tree's dicts/lists line up with the live skeleton's
    namedtuples/tuples.  Params and optimizer state carry *logical*
    shapes (world-size independent) and transfer value-exact; sync state
    (leading device axis) reinitializes; a bounded-staleness storage
    leaf ``(n_old,) + s`` collapses to copy 0 and re-broadcasts to the
    new device count — per-device divergent copies cannot survive a
    topology change.  Placement (including re-padding for the new
    mesh's uneven-shard plan) happens through the runner's own
    ``from_logical``/sharding machinery.
    """
    skel = _prune_sync_state(
        jax.eval_shape(lambda: runner.to_logical(runner.create_state())))
    raw_by_path = {
        name: np.asarray(leaf) for name, leaf
        in manifest_mod.leaves_by_path(raw).items()}
    n_new = runner.program.data_axis_size

    def pick(prefix, skel_tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(skel_tree)
        out = []
        for path, want in flat:
            name = f"{prefix}/{path_to_name(path)}" if path else prefix
            got = raw_by_path.get(name)
            if got is None:
                raise ManifestMismatchError(
                    f"autodist_tpu: cross-shape restore: checkpoint has no "
                    f"leaf at {name!r} (the manifest validation should have "
                    f"caught this — was the checkpoint edited?)")
            want_shape = tuple(want.shape)
            if got.shape != want_shape:
                # Leading-device-axis storage (bounded staleness): the
                # per-device copies collapse to copy 0 on a new topology.
                if (saved_data_axis and got.ndim == len(want_shape)
                        and got.shape[1:] == want_shape[1:]
                        and got.shape[0] == saved_data_axis
                        and want_shape[0] == n_new):
                    got = np.broadcast_to(got[0], want_shape).copy()
                else:
                    raise ManifestMismatchError(
                        f"autodist_tpu: cross-shape restore: leaf {name!r} "
                        f"was saved with shape {tuple(got.shape)} but the "
                        f"live model expects {want_shape} — logical shapes "
                        f"must be mesh-independent")
            out.append(got.astype(np.dtype(want.dtype), copy=False))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = pick("params", skel.params)
    opt_state = pick("opt_state", skel.opt_state)
    step = np.asarray(raw_by_path.get("step", 0), np.int32)
    sync_state = {}
    for k, v in skel.sync_state.items():
        if jax.tree_util.tree_leaves(v):
            logging.warning("cross-shape restore reinitializes sync state "
                            "for %s (device-resident residuals do not "
                            "survive a topology change)", k)
            sync_state[k] = runner.fresh_sync_state(k)
        else:
            sync_state[k] = v
    logical = TrainState(step=step, params=params, opt_state=opt_state,
                         sync_state=sync_state)
    logical = _rebuild_sync_state(runner, logical)
    if runner._paddings:
        return runner.from_logical(logical)
    return jax.device_put(logical, runner.state_shardings)


def reshard_live_state(runner, state, new_program):
    """Re-lay-out a LIVE TrainState onto a different program on the same
    mesh — the online re-tuning controller's tier-2 switch path
    (docs/retuning.md), reusing the elastic cross-shape machinery with
    no checkpoint in the middle.

    The state snapshots to host numpy at *logical* shapes through the
    OLD program's ``to_logical`` (value-exact, layout-free), the runner
    adopts ``new_program`` (shardings, paddings, jit caches all rebuilt),
    and :func:`reshard_state` places every leaf per the new plan —
    including re-padding for the new uneven-shard layout and sync-state
    reinitialization, exactly as an elastic restore would.
    """
    logical = runner.to_logical(state)
    raw = jax.tree_util.tree_map(np.asarray, jax.device_get(logical))
    old_axis = int(runner.program.data_axis_size)
    runner._adopt_program(new_program)
    return reshard_state(runner, raw, saved_data_axis=old_axis)


def _restore_raw_host(path):
    """Topology-free read: the checkpoint as a host-numpy pytree.

    The cross-shape path cannot use ``StandardRestore`` with no target —
    that materializes arrays onto the SAVE-time device set, which no
    longer exists after a real shrink (the tier-1 forced-device harness
    masks this: all 8 devices still exist when a test carves a 4-device
    mesh out of them).  A PyTree restore with
    ``restore_type=np.ndarray`` never touches devices at all.
    """
    path = str(path)
    default = os.path.join(path, "default")
    if os.path.isdir(default):  # CheckpointManager step dirs nest the item
        path = default
    ckptr = ocp.PyTreeCheckpointer()
    # metadata() is a StepMetadata; the saved pytree's structure is the
    # ``tree`` of its item metadata.
    restore_args = jax.tree_util.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray),
        ckptr.metadata(path).item_metadata.tree)
    return ckptr.restore(
        path, args=ocp.args.PyTreeRestore(restore_args=restore_args))


def _reshard_restore(runner, manifest, raw_restore_fn, where=""):
    """Run one cross-shape (elastic) restore: raw-read the checkpoint,
    rebuild the state on the current mesh, and record the reshard as a
    first-class event (flight recorder + ``checkpoint.reshard_ms`` /
    ``cluster.world_size`` gauges)."""
    from autodist_tpu import resilience
    world = manifest.get("world", {})
    mesh = runner.program.mesh
    cur_devices = int(np.prod(list(mesh.shape.values()))) if mesh.shape else 1
    t0 = time.perf_counter()
    with observability.span("restore", where=str(where), reshard=True):
        raw = raw_restore_fn()
        state = reshard_state(runner, raw,
                              saved_data_axis=world.get("data_axis"))
        # The reshard is only done once the new placements exist.
        jax.block_until_ready(jax.tree_util.tree_leaves(state.params))
    dt_ms = (time.perf_counter() - t0) * 1e3
    try:
        processes = jax.process_count()
    except Exception:  # noqa: BLE001
        processes = 1
    detail = (f"step {int(np.asarray(jax.device_get(state.step)))}: "
              f"world {world.get('devices')}d/{world.get('processes')}p "
              f"-> {cur_devices}d/{processes}p in {dt_ms:.0f}ms")
    resilience.record_event("reshard", detail)
    observability.record_event("checkpoint-restore", f"resharded: {detail}")
    logging.info("cross-shape restore: %s", detail)
    if observability.enabled():
        reg = observability.registry()
        reg.gauge("checkpoint.reshard_ms").set(round(dt_ms, 3))
        reg.gauge("cluster.world_size").set(processes)
    return state


def _params_subtree(tree):
    """Params subtree of a raw-restored checkpoint pytree.

    A training-written checkpoint restores as a TrainState-shaped dict
    (``{step, params, opt_state, sync_state}``); a params-only artifact
    (e.g. from ``Saver.save(params, ...)``) IS the params tree already.
    Serving restores through this so it never has to reconstruct an
    optimizer to describe the optimizer-state subtree it does not want.
    """
    if isinstance(tree, dict) and "params" in tree and "step" in tree:
        return tree["params"]
    if hasattr(tree, "params") and hasattr(tree, "step"):  # live TrainState
        return tree.params
    return tree


def _abstract_state(runner):
    """ShapeDtypeStruct pytree of the runner's *logical* TrainState.

    Checkpoints always hold logical shapes (uneven-sharded variables are
    stored padded on device but unpadded on disk, keeping checkpoints
    mesh-portable).  A leaf whose logical shape the plan's sharding cannot
    tile evenly restores replicated and is re-padded by ``from_logical``.
    """
    state_shapes = _prune_sync_state(
        jax.eval_shape(lambda: runner.to_logical(runner.create_state())))
    shardings = _prune_sync_state(runner.state_shardings)

    def leaf(s, sh):
        try:
            sh.shard_shape(tuple(s.shape))  # raises if not evenly tileable
        except Exception:  # noqa: BLE001
            sh = jax.sharding.NamedSharding(sh.mesh, jax.sharding.PartitionSpec())
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return jax.tree_util.tree_map(leaf, state_shapes, shardings)


class Saver:
    """Save/restore full training state (params + optimizer + step).

    Like the reference saver (must exist before the session is built,
    ``saver.py:63-66``), a Saver binds to a Runner — it needs the sharding
    plan to restore onto the live mesh.
    """

    def __init__(self, runner=None):
        self._runner = runner
        self._ckptr = ocp.StandardCheckpointer()

    def save(self, state, path, force=True):
        """Write ``state`` (TrainState or bare params pytree) to ``path``.
        Transient filesystem faults retry with backoff (resilience/retry).
        TrainState saves get a layout-independent manifest sidecar
        (``<path>.manifest.json``) so the checkpoint restores onto a
        different world size (docs/elasticity.md)."""
        path = os.path.abspath(path)
        is_state = isinstance(state, TrainState)
        if self._runner is not None and is_state:
            state = _prune_sync_state(self._runner.to_logical(state))
        with observability.span("checkpoint-save", path=path):
            retry_call(self._ckptr.save, path, state, force=force,
                       is_retryable=transient_runtime_error,
                       describe="checkpoint save")
            self._ckptr.wait_until_finished()
        if self._runner is not None and is_state:
            step = int(np.asarray(jax.device_get(state.step)))
            manifest_mod.write_manifest(self._runner, step,
                                        manifest_mod.sidecar_path(path))
        observability.record_event("checkpoint-save", path)
        logging.info("saved checkpoint %s", path)
        return path

    def restore(self, path):
        """Restore onto the bound runner's mesh/shardings (resharding OK).

        With a manifest sidecar present, the restore is topology-elastic:
        a world-size change since save time routes through the
        cross-shape reshard path (value-exact params/optimizer state on
        the new mesh), and a manifest whose pytree paths do not match
        the live model raises :class:`ManifestMismatchError` instead of
        a deep orbax failure."""
        if self._runner is None:
            raise ValueError("restore() needs a Runner; use restore_raw() for "
                             "framework-free reads")
        path = os.path.abspath(path)
        man = manifest_mod.read_manifest(manifest_mod.sidecar_path(path))
        if man is not None:
            manifest_mod.validate_manifest(man, self._runner, where=path)
        if man is not None and manifest_mod.world_changed(man, self._runner):
            return _reshard_restore(
                self._runner, man,
                lambda: retry_call(
                    _restore_raw_host, path,
                    is_retryable=transient_runtime_error,
                    describe="cross-shape checkpoint restore"),
                where=path)
        with observability.span("restore", path=path):
            abstract = _abstract_state(self._runner)
            state = retry_call(self._ckptr.restore, path, abstract,
                               is_retryable=transient_runtime_error,
                               describe="checkpoint restore")
            state = _rebuild_sync_state(self._runner, state)
            state = self._runner.from_logical(state)
        observability.record_event("checkpoint-restore", path)
        logging.info("restored checkpoint %s", path)
        return state

    def restore_raw(self, path):
        """Framework-free read: the checkpoint as a host-numpy pytree
        (topology-free — readable from any device count)."""
        path = os.path.abspath(path)
        restored = _restore_raw_host(path)
        return jax.tree_util.tree_map(np.asarray, restored)

    def restore_params(self, path):
        """Params-only restore: the model parameters as a host-numpy
        pytree, with NO optimizer required or reconstructed.

        Works on both training-written checkpoints (the full TrainState
        tree — step/opt_state/sync_state are read raw and discarded) and
        params-only artifacts.  This is the serving restore path
        (docs/serving.md): hand the result to ``serve.Server`` (or
        ``Remapper.place_params``) — placement is the engine's job, not
        the checkpoint's.  Needs no bound Runner.
        """
        with observability.span("restore", path=path, params_only=True):
            params = jax.tree_util.tree_map(
                np.asarray, _params_subtree(self.restore_raw(path)))
        observability.record_event("checkpoint-restore",
                                   f"{path} (params only)")
        logging.info("restored params-only checkpoint %s", path)
        return params


class CheckpointManager:
    """Periodic checkpointing + resume (preemption tolerance).

    The reference has no elastic recovery (worker death ⇒ ``os._exit(1)``,
    ``coordinator.py:98-110``); on TPU preemption is routine, so periodic
    save + latest-step resume is first-class. Orbax handles retention and
    multi-host coordination.
    """

    def __init__(self, runner, directory=None, save_interval_steps=100,
                 max_to_keep=3):
        self._runner = runner
        self._dir = os.path.abspath(directory or const.DEFAULT_CHECKPOINT_DIR)
        self._interval = save_interval_steps
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps))

    @property
    def directory(self):
        return self._dir

    def save(self, step, state, force=False):
        if not force and not self._mgr.should_save(step):
            return False  # skip the logical conversion on non-save steps
        if isinstance(state, TrainState):
            state = _prune_sync_state(self._runner.to_logical(state))
        t0 = time.perf_counter()
        with observability.span("checkpoint-save", step=step):
            saved = retry_call(
                self._mgr.save, step, args=ocp.args.StandardSave(state),
                force=force, is_retryable=transient_runtime_error,
                describe=f"checkpoint save (step {step})")
        if saved:
            # Layout-independent manifest next to the step dir (the
            # array write may still be in flight; the manifest only
            # describes structure, which is known now).  Chief-only,
            # fail-open; stale manifests of evicted steps are pruned.
            manifest_mod.write_manifest(
                self._runner, step, self._manifest_path(step))
            self._prune_manifests()
        if saved and observability.enabled():
            reg = observability.registry()
            reg.counter("checkpoint.saves").inc()
            reg.gauge("checkpoint.last_save_ms").set(
                round((time.perf_counter() - t0) * 1e3, 3))
            observability.record_event("checkpoint-save", f"step {step}")
        return saved

    def _manifest_path(self, step):
        return os.path.join(self._dir, manifest_mod.manifest_name(step))

    def _prune_manifests(self):
        """Drop manifests whose step dir orbax already evicted."""
        try:
            if jax.process_index() != 0:
                return
            live = {int(s) for s in self._mgr.all_steps()}
            for fname in os.listdir(self._dir):
                if not (fname.startswith("manifest-")
                        and fname.endswith(".json")):
                    continue
                stem = fname[len("manifest-"):-len(".json")]
                if stem.isdigit() and int(stem) not in live:
                    os.remove(os.path.join(self._dir, fname))
        except OSError:  # noqa: BLE001 - hygiene only, never kill a save
            pass

    def latest_step(self):
        return self._mgr.latest_step()

    def restore_params(self, step=None):
        """Params-only restore from a managed (training-written)
        checkpoint: the model parameters at ``step`` (default: the
        latest retained step) as a host-numpy pytree, without touching —
        or needing to describe — the optimizer-state subtree.

        The raw (target-free) orbax restore sidesteps the abstract-state
        machinery entirely, so serving can load a checkpoint written by
        a training job whose optimizer it has no way (and no reason) to
        reconstruct.
        """
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            raise ValueError(
                f"no checkpoint steps under {self._dir}; nothing to "
                f"restore params from")
        with observability.span("restore", step=step, params_only=True):
            raw = retry_call(
                self._mgr.restore, step, args=ocp.args.StandardRestore(),
                is_retryable=transient_runtime_error,
                describe=f"params-only restore (step {step})")
            params = jax.tree_util.tree_map(np.asarray, _params_subtree(raw))
        observability.record_event("checkpoint-restore",
                                   f"step {step} (params only)")
        logging.info("restored params-only checkpoint step %d", step)
        return params

    def wait_until_finished(self):
        """Block until pending (async) saves are durable."""
        self._mgr.wait_until_finished()

    def restore_or_init(self):
        """Resume from the newest INTACT checkpoint, or create fresh state.

        Integrity is verified on restore (orbax surfaces torn/truncated
        step dirs as restore errors, and the restored ``step`` leaf — the
        sentinel — must match the directory it came from); a corrupt step
        falls back to the previous retained one instead of killing the
        relaunch, because the likeliest cause is this very job's earlier
        incarnation dying mid-write.
        """
        from autodist_tpu import resilience
        steps = sorted(self._mgr.all_steps())
        for step in reversed(steps):
            man = manifest_mod.read_manifest(self._manifest_path(step))
            if man is not None:
                # Model mismatch is a user error, not corruption: raise
                # loudly instead of falling back to older steps (which
                # would share the mismatch) or silently training fresh.
                manifest_mod.validate_manifest(
                    man, self._runner, where=f"step {step} in {self._dir}")
            try:
                if man is not None and \
                        manifest_mod.world_changed(man, self._runner):
                    # Elastic resume: the world size changed since save
                    # time — reshard every leaf onto the current mesh
                    # (docs/elasticity.md).
                    state = _reshard_restore(
                        self._runner, man,
                        lambda step=step: retry_call(
                            _restore_raw_host,
                            os.path.join(self._dir, str(step)),
                            is_retryable=transient_runtime_error,
                            describe=f"cross-shape restore (step {step})"),
                        where=f"step {step}")
                else:
                    with observability.span("restore", step=step):
                        abstract = _abstract_state(self._runner)
                        state = retry_call(
                            self._mgr.restore, step,
                            args=ocp.args.StandardRestore(abstract),
                            is_retryable=transient_runtime_error,
                            describe=f"checkpoint restore (step {step})")
                    state = _rebuild_sync_state(self._runner, state)
                    state = self._runner.from_logical(state)
                restored_step = int(jax.device_get(
                    jax.tree_util.tree_leaves(state.step)[0]))
                if restored_step != step:
                    raise ValueError(
                        f"checkpoint step sentinel mismatch: directory "
                        f"{step} holds state.step={restored_step}")
            except KeyboardInterrupt:
                raise
            except ManifestMismatchError:
                raise
            except Exception as e:  # noqa: BLE001 - corruption is open-ended
                resilience.record_event(
                    "ckpt-fallback",
                    f"step {step} unrestorable ({type(e).__name__}: "
                    f"{str(e)[:200]}); trying previous retained step")
                logging.warning("checkpoint step %d unrestorable (%s); "
                                "falling back to the previous retained step",
                                step, e)
                continue
            if observability.enabled():
                observability.registry().counter("checkpoint.restores").inc()
                observability.record_event("checkpoint-restore",
                                           f"resumed step {step}")
            logging.info("resumed from checkpoint step %d", step)
            return state
        if steps:
            logging.warning("no retained checkpoint was restorable; "
                            "initializing fresh state")
        return self._runner.create_state()

    def run(self, state, data_iter, num_steps, step_guard=None,
            preemption=None, coordinator=None, unroll=None):
        """Step loop with periodic checkpointing; resumes mid-run after
        preemption when called again (state from :meth:`restore_or_init`).

        Resilience wiring (all optional, all off by default):

        * ``step_guard`` (:class:`~autodist_tpu.resilience.StepGuard`):
          host-checks the device-side ``notfinite`` flag every
          ``check_every`` steps AND before every periodic save (a
          poisoned state must never be persisted); on divergence restores
          the latest checkpoint and continues with fresh batches.
        * ``preemption`` (:class:`~autodist_tpu.resilience.
          PreemptionHandler`): ``True`` installs a handler for the loop's
          duration; a SIGTERM/SIGINT then force-saves an emergency
          checkpoint at the current step and raises
          :class:`~autodist_tpu.resilience.Preempted`.
        * ``coordinator``: under the checkpoint-and-exit supervision
          policy, a worker death observed by the chief's Coordinator
          drains this loop through the same emergency-save path (raises
          ``RuntimeError``).

        ``unroll=K`` (env ``AUTODIST_UNROLL``) fuses K steps per XLA
        dispatch (``Runner.megastep``); saves, preemption polls, and
        guard checks all land on megastep boundaries.  A resume whose
        start step is not K-aligned single-steps up to the next boundary
        first, so checkpoints stay consistent at megastep granularity.
        """
        from autodist_tpu.resilience import PreemptionHandler
        metrics = None
        start = int(jax.device_get(state.step)) if isinstance(state, TrainState) else 0
        if unroll is None:
            unroll = const.ENV.AUTODIST_UNROLL.val
        unroll = max(1, int(unroll))
        chaos = None
        if const.ENV.AUTODIST_CHAOS.val:
            from autodist_tpu.resilience import chaos
        handler = preemption
        installed = False
        if handler is True:
            handler = PreemptionHandler().install()
            installed = True
        # Same telemetry discipline as Runner._run_observed: one clock
        # read + list append per step, registry flush on the guard
        # cadence; zero telemetry calls when AUTODIST_TELEMETRY=0.
        obs = self._runner._obs
        cadence = (step_guard.check_every if step_guard is not None
                   else max(1, const.ENV.AUTODIST_GUARD_CHECK_EVERY.val))
        if unroll > 1:
            # Megastep granularity: checks/saves happen at dispatch
            # boundaries, so the cadence rounds up to a multiple of K.
            cadence = ((cadence + unroll - 1) // unroll) * unroll
        pending = []  # (host wall-clock delta, steps covered) per dispatch
        # Online re-tuning + self-healing (docs/retuning.md): the
        # checkpoint-managed loop is where a coordinator exists, so it is
        # where reshape-on-degrade can act — bind the coordinator so the
        # controller's tier-2 candidate set keeps different-mesh
        # challengers (executed through the elastic re-exec below), and
        # arm the degraded-host healer.  Unroll switching is withheld:
        # this loop owns its own block alignment.
        retune_ctl = None
        selfheal_mod = None
        last_window = {}
        if obs is not None:
            try:
                from autodist_tpu import retune as retune_mod
                from autodist_tpu.retune import selfheal as selfheal_mod
                if retune_mod.enabled():
                    retune_mod.bind_coordinator(coordinator)
                    selfheal_mod.bind(self, coordinator)
                    retune_ctl = retune_mod.controller_for(
                        self._runner, unroll=unroll, allow_unroll=False)
                else:
                    selfheal_mod = None
            except Exception as e:  # noqa: BLE001 - must not kill runs
                logging.debug("retune controller unavailable: %s", e)
                retune_ctl, selfheal_mod = None, None

        def _flush_steps():
            if not pending:
                return
            if retune_ctl is not None or selfheal_mod is not None:
                lat = sorted(dt * 1e3 / st for dt, st in pending)
                last_window["p50_ms"] = lat[len(lat) // 2]
            reg = observability.registry()
            reg.histogram("step.latency_ms").observe_many(
                [dt * 1e3 / st for dt, st in pending])
            reg.counter("step.count").inc(sum(st for _, st in pending))
            pending.clear()

        # Same step-loop span Runner.run opens: the goodput ledger keys
        # its in-loop-vs-outside accounting (compiles and saves billed
        # into step latency) on this container span.  Entered manually so
        # the existing try/finally stays the single unwind point.
        loop_span = observability.span("step-loop", steps=num_steps,
                                       unroll=unroll)
        loop_span.__enter__()
        try:
            import time as _time
            i = start
            t_prev = _time.perf_counter() if obs is not None else 0.0
            while i < num_steps:
                # Fused K-step dispatch when aligned and a whole block
                # remains; single steps align an unaligned resume head
                # and drain any sub-K tail.
                k = (unroll if unroll > 1 and i % unroll == 0
                     and num_steps - i >= unroll else 1)
                if k > 1:
                    block = self._runner._next_block(data_iter, k)
                    if chaos is not None:
                        block = chaos.maybe_poison_batch(i + 1, block)
                    state, metrics = self._runner.megastep(state, block)
                else:
                    batch = next(data_iter)
                    if chaos is not None:
                        batch = chaos.maybe_poison_batch(i + 1, batch)
                    state, metrics = self._runner.step(state, batch)
                i += k
                if obs is not None:
                    t_now = _time.perf_counter()
                    pending.append((t_now - t_prev, k))
                    t_prev = t_now
                    if i % cadence == 0 or i >= num_steps:
                        _flush_steps()
                        if selfheal_mod is not None:
                            # Cheap healer bookkeeping: where the run is
                            # (remaining-steps pricing) and how fast it
                            # currently goes.
                            selfheal_mod.note_progress(
                                i, num_steps, last_window.get("p50_ms"))
                if chaos is not None:
                    chaos.maybe_kill(i)
                    chaos.maybe_slow_host(i)
                if handler:
                    handler.check(self, i, state)  # raises Preempted
                if coordinator is not None and \
                        getattr(coordinator, "reform_pending", False):
                    # Elastic supervision: drain to an emergency
                    # checkpoint and re-form at the new world size
                    # instead of aborting (docs/elasticity.md).  Flush
                    # billed steps first so the goodput segment this
                    # generation persists carries them.
                    if obs is not None:
                        _flush_steps()
                    self._elastic_drain(i, state, coordinator)
                if coordinator is not None and coordinator.failed:
                    if obs is not None:
                        _flush_steps()
                    with observability.span("emergency-save", step=i,
                                            why="worker-death"):
                        self.save(i, state, force=True)
                        self._mgr.wait_until_finished()
                    raise RuntimeError(
                        "autodist_tpu: a worker died (checkpoint-and-exit "
                        f"supervision); emergency checkpoint at step {i}")
                if step_guard is not None and (
                        i % cadence == 0 or i >= num_steps
                        or self._mgr.should_save(i)):
                    if step_guard.diverged(metrics):
                        i, state = step_guard.rollback(i, manager=self)
                        if obs is not None:
                            pending.clear()  # don't bill rollback as steps
                            t_prev = _time.perf_counter()
                        continue
                    step_guard.progressed()
                if retune_ctl is not None and i < num_steps and \
                        (i % cadence == 0 or retune_ctl.eval_requested()):
                    if obs is not None and pending and \
                            retune_ctl.eval_requested():
                        _flush_steps()  # out-of-cadence: price the
                        #                 partial window first
                    if last_window.get("p50_ms") is not None:
                        state = self._maybe_retune_managed(
                            retune_ctl, state, i, num_steps, last_window)
                self.save(i, state)
            self._mgr.wait_until_finished()
        finally:
            loop_span.__exit__(None, None, None)
            if installed:
                handler.uninstall()
        if obs is not None:
            try:
                # Run-level goodput/MFU ledger (docs/goodput.md) — same
                # cold-path finalize Runner._run_observed performs.
                from autodist_tpu.observability import goodput as goodput_mod
                goodput_mod.finalize(self._runner, observability.registry())
            except Exception as e:  # noqa: BLE001
                logging.debug("goodput not recorded: %s", e)
        return state, metrics

    def _maybe_retune_managed(self, ctl, state, i, num_steps, last_window):
        """Consult the online re-tuning controller inside the checkpoint-
        managed loop (docs/retuning.md).  In-place tier-1/tier-2 switches
        apply directly (unroll is withheld, so block alignment is
        untouched); a *reshape* decision pins the challenger on the
        coordinator and requests a re-form — the ``reform_pending`` poll
        above drains it through emergency-save + re-exec.  Fail-open,
        except a shipped-verdict mismatch, which must surface."""
        try:
            decision = ctl.observe_window(last_window["p50_ms"],
                                          remaining_steps=num_steps - i,
                                          step=i)
        except Exception as e:  # noqa: BLE001 - evaluation must not kill
            from autodist_tpu.retune import shipping
            if isinstance(e, shipping.ShipMismatch):
                raise
            logging.warning("retune evaluation failed (run continues): %s",
                            e)
            return state
        if decision is None:
            return state
        try:
            state, _ = ctl.apply(state, decision, step=i)
        except Exception as e:  # noqa: BLE001 - switch must not kill
            from autodist_tpu.retune import shipping
            if isinstance(e, shipping.ShipMismatch):
                raise
            logging.warning("retune switch failed (run continues): %s", e)
        return state

    def _elastic_drain(self, step, state, coordinator):
        """Elastic re-form observed by the chief's step loop: emergency-
        save when the state is still recoverable, then hand control to
        ``Coordinator.reform_now`` (which re-execs the job at the new
        world size — on a stubbed exec this raises
        :class:`~autodist_tpu.resilience.ElasticReform` so callers/tests
        unwind cleanly).

        The emergency save only runs single-process: after a participant
        died, a multi-process job can neither dispatch nor barrier-save
        global arrays — the relaunch then resumes from the last retained
        periodic checkpoint instead (same worst-case loss contract as
        preemption: one save interval).
        """
        from autodist_tpu import resilience
        from autodist_tpu.resilience import ElasticReform
        try:
            processes = jax.process_count()
        except Exception:  # noqa: BLE001
            processes = 1
        if processes == 1:
            with observability.span("emergency-save", step=step,
                                    why="elastic-re-form"):
                self.save(step, state, force=True)
                self._mgr.wait_until_finished()
            resilience.record_event(
                "emergency-save", f"elastic re-form: checkpoint at step "
                                  f"{step} before shrinking")
        else:
            resilience.record_event(
                "emergency-save",
                "skipped: multi-process state is not chief-recoverable "
                "after a participant death; re-forming from the last "
                "retained checkpoint")
        if observability.enabled():
            try:
                # Close out this generation's goodput ledger before the
                # re-exec replaces the process: the persisted segment's
                # end timestamp bounds the re-exec gap the surviving
                # chief prices when it stitches the run back together.
                from autodist_tpu.observability import goodput as goodput_mod
                goodput_mod.finalize(self._runner, observability.registry())
            except Exception as e:  # noqa: BLE001
                logging.debug("goodput not recorded before re-form: %s", e)
        coordinator.reform_now()
        raise ElasticReform(new_world=coordinator.world_size, step=step)

    def close(self):
        self._mgr.wait_until_finished()
        self._mgr.close()
