"""Shifting-scan pipeline schedules: GPipe microbatching over the pipe axis.

The GSPMD construction (arxiv 2105.04663 §3.3): every device runs the same
program holding ONE stage's parameters (stage-stacked pytree, leading dim
sharded over ``pipe``); activations hop stage-to-stage with ``lax.ppermute``
while microbatches stream in.  Reverse-mode autodiff through the
scan/ppermute schedule yields the backward pipeline for free.

Two schedules share one local executor:

* ``"shift"`` (default) — the pipelined schedule.  Stage r computes real
  work at ticks t in [r, r+M); fill/drain slots are SKIPPED via
  ``lax.cond`` (no garbage FLOPs).  Wall-clock bubble fraction is the
  classic GPipe (P-1)/(M+P-1).
* ``"sequential"`` — the *unpipelined control arm*: each microbatch
  traverses all P stages before the next one enters (tick t activates
  stage t mod P on microbatch t // P; M*P ticks).  Same stage placement,
  same per-tick collectives, same gradient-accumulation order — so the
  shifting schedule is pinned BITWISE against it (tests/test_pipeline.py),
  isolating exactly the overlap.  Select via
  ``AUTODIST_PIPELINE_SCHEDULE=sequential`` for numerics debugging.

Outputs: when M % P == 0 the finished microbatches ride a second rotating
``done`` conveyor and each rank commits the microbatches with
m mod P == rank — the result leaves the shard_map SHARDED over ``pipe``
(out_specs carries the pipe axis).  No full-buffer broadcast: downstream
GSPMD either all-gathers on demand ((P-1)/P of the payload, half a psum's
cost) or keeps head/loss compute sharded over ``pipe``.  The conveyor
extends the shifting scan to M + 2P - 3 ticks; the extra P-2 ticks are
compute-skipped (ppermute only).  With M % P != 0 the legacy last-stage
buffer + psum broadcast is used (M + P - 1 ticks).

Manual axes: the shard_map goes manual over ``pipe`` AND — when the mesh
carries a plain data axis, the microbatch rows divide it, and no
sequence-parallel composition is active — over ``data`` as well, making
the region FULL-manual.  Batch-row semantics are unchanged (stage compute
is row-independent; the gradient psum over ``data`` moves from GSPMD into
shard_map's transpose), and a full-manual region leaves nothing for the
SPMD partitioner to split inside the schedule.  The
seq-parallel composition keeps ``data`` auto (one manual region over
{pipe, seq}; see ``pipeline_apply``'s seq_axis note).

Constraints (the standard collective-pipeline shape): all stages share one
activation shape — put the embedding before and the head after the
pipelined block stack; stage count = mesh's ``pipe`` axis size; microbatch
count >= stages to bound the bubble fraction.
"""
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from autodist_tpu import const

#: ``shift`` — the pipelined schedule; ``sequential`` — the bitwise
#: unpipelined control arm; ``shift-noskip`` — shift with the fill/drain
#: compute skip disabled (every idle slot executes garbage work), the
#: arm that pairs against ``shift`` to turn the schedule's idle-slot
#: share into wall-clock (no caller pairs them today: ROADMAP D7);
#: ``1f1b`` — shift with the stage body rematerialized in backward, so
#: the scan retains only stage-boundary activations: the resident hold
#: drops from GPipe's all-M to 1F1B's min(S, M) in-flight depth
#: (strategy_memory's ``hold_depth`` prices exactly this).
SCHEDULES = ("shift", "sequential", "shift-noskip", "1f1b")


def resolve_skip_idle(backend=None, seq_manual=False):
    """Resolved default for ``skip_idle=None`` (the per-backend contract
    a regression test pins, ROADMAP 3d):

    * sequence-parallel composition => **off**: ``lax.cond`` cannot wrap
      the stage's manual seq-axis collectives (ring/all_to_all inside a
      conditional aborts XLA's rendezvous);
    * XLA:CPU => **off**: the cond's TRANSPOSE under reverse-mode AD
      lowers to full select chains, measured SLOWER than the garbage
      fill/drain compute the skip avoids (a skip-vs-noskip pair, timed
      on the CPU container);
    * every other backend (TPU/GPU) => **on**: fill/drain slots skip
      their stage compute, erasing the bubble's FLOPs.
    """
    if seq_manual:
        return False
    if backend is None:
        backend = jax.default_backend()
    return str(backend).lower() != "cpu"


def stack_stage_params(stage_params_list):
    """[per-stage pytree, ...] -> one pytree with a leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params_list)


def bubble_fraction(p_size, num_microbatches, sharded_commit=None):
    """Idle-slot fraction of the shifting schedule.

    The classic GPipe wall-clock bubble is ``(P-1)/(M+P-1)``; when the
    round-robin output conveyor is in play (``sharded_commit=True``) the
    scan runs M + 2P - 3 ticks of which M are compute ticks per rank, so
    the idle fraction is ``(2P-3)/(M+2P-3)`` — identical at P=2, and the
    share of ticks tests/test_pipeline.py counts as idle.
    With ``sharded_commit=None`` the classic model is returned.
    """
    if sharded_commit:
        ticks = num_schedule_steps(p_size, num_microbatches, True)
        return (ticks - num_microbatches) / ticks
    return (p_size - 1) / (num_microbatches + p_size - 1)


def num_schedule_steps(p_size, num_microbatches, sharded_commit,
                       schedule="shift"):
    """Static scan trip count of a schedule (pinned by tests)."""
    if schedule == "sequential":
        return num_microbatches * p_size
    if sharded_commit:
        return num_microbatches + 2 * p_size - 3
    return num_microbatches + p_size - 1


def _pipeline_local(stage_params, stage_fn, x_micro, axis_name, p_size,
                    stage, sharded_commit, skip_idle=True, schedule="shift"):
    """Runs inside the manual-over-pipe context.

    stage_params: this stage's params (leading stage dim of size 1).
    x_micro: (M, mb, ...) microbatches (replicated over pipe; the mb dim
    may be manual over data).
    ``p_size``/``stage`` come from the wrapper (static size + sharded-iota
    index: ``lax.axis_index`` cannot lower in nested partial-manual regions).
    Returns (M, mb, ...) outputs replicated over pipe (legacy path) or
    (M/P, mb, ...) per-rank round-robin commits (sharded path, M % P == 0).
    """
    my_params = jax.tree_util.tree_map(lambda l: l[0], stage_params)
    num_micro = x_micro.shape[0]
    n_local = num_micro // p_size if sharded_commit else num_micro

    # Derive varying-typed zero buffers from params AND inputs so the scan
    # carry type is stable (same VMA trick as ring attention): params make
    # the carry pipe-varying, x_micro makes it seq-varying when the region
    # is manual over seq too.
    pzero = sum(jnp.sum(l) * 0.0 for l in jax.tree_util.tree_leaves(my_params))
    pzero = pzero + jnp.sum(x_micro).astype(jnp.float32) * 0.0
    act0 = jnp.zeros(x_micro.shape[1:], x_micro.dtype) + \
        pzero.astype(x_micro.dtype)
    outs0 = jnp.zeros((n_local,) + x_micro.shape[1:], x_micro.dtype) + \
        pzero.astype(x_micro.dtype)

    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    sequential = schedule == "sequential"

    def step(carry, t):
        act, done, outs = carry
        if sequential:
            # Unpipelined: one microbatch in flight — stage r computes
            # microbatch t // P exactly at tick t with t mod P == r.
            m_feed = t // p_size
            m_in = jnp.where(t % p_size == stage, m_feed, -1)
        else:
            # Pipelined: stage r's input at tick t is microbatch t - r.
            m_feed = t
            m_in = t - stage
        feed = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(m_feed, 0, num_micro - 1), 0, keepdims=False)
        inp = jnp.where(stage == 0, feed, act)
        valid_in = jnp.logical_and(m_in >= 0, m_in < num_micro)
        # Anything else is fill/drain garbage — skip the stage compute
        # entirely (identity passthrough).  The named scopes give the
        # per-layer profiler a handle on stage compute vs schedule
        # machinery (docs/pipelining.md).
        with jax.named_scope("stage"):
            if skip_idle:
                y = lax.cond(valid_in,
                             lambda i: stage_fn(my_params, i),
                             lambda i: i, inp)
            else:
                y = stage_fn(my_params, inp)

        if sharded_commit:
            # A finished microbatch m leaves the last stage (at tick
            # m + P - 1 shifting, m*P + P - 1 sequential) and rides the
            # ``done`` conveyor: rank r < P-1 receives it P - 1 + (r+1)
            # hops ... later; rank r commits the microbatches with
            # m mod P == r.  The last stage commits its own share directly.
            commit_val = jnp.where(stage == p_size - 1, y, done)
            if sequential:
                m_c = jnp.where(
                    stage == p_size - 1,
                    jnp.where(t % p_size == p_size - 1, t // p_size, -1),
                    jnp.where((t - p_size - stage) % p_size == 0,
                              (t - p_size - stage) // p_size, -1))
            else:
                m_c = jnp.where(stage == p_size - 1, t - (p_size - 1),
                                t - p_size - stage)
            valid = jnp.logical_and(
                jnp.logical_and(m_c >= 0, m_c < num_micro),
                m_c % p_size == stage)
            slot = jnp.clip(m_c // p_size, 0, n_local - 1)
            done = commit_val
        else:
            # Legacy: last stage accumulates every microbatch; broadcast after.
            commit_val = y
            if sequential:
                m_c = jnp.where(t % p_size == p_size - 1, t // p_size, -1)
            else:
                m_c = t - (p_size - 1)
            valid = jnp.logical_and(stage == p_size - 1,
                                    jnp.logical_and(m_c >= 0,
                                                    m_c < num_micro))
            slot = jnp.clip(m_c, 0, n_local - 1)

        with jax.named_scope("shift"):
            cur = lax.dynamic_index_in_dim(outs, slot, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, commit_val, cur), slot, 0)
            act, done = jax.tree_util.tree_map(
                lambda z: lax.ppermute(z, axis_name, perm), (y, done))
        return (act, done, outs), None

    steps = num_schedule_steps(p_size, num_micro, sharded_commit, schedule)
    (_, _, outs), _ = lax.scan(step, (act0, act0, outs0), jnp.arange(steps))
    if not sharded_commit:
        # Broadcast the last stage's buffer to every pipe member.
        outs = lax.psum(jnp.where(stage == p_size - 1, outs, 0.0), axis_name)
    return outs


def pipeline_apply(stage_params, stage_fn, x, num_microbatches, mesh,
                   axis_name=const.MESH_AXIS_PIPELINE,
                   seq_axis=None, seq_dim=None, skip_idle=None,
                   schedule="shift"):
    """Apply a stack of pipelined stages to a batch.

    Args:
        stage_params: pytree whose leaves have leading dim = #stages
            (``stack_stage_params``); sharded over ``axis_name``.
        stage_fn: ``(params_one_stage, activation) -> activation`` with a
            shape-preserving activation.
        x: (batch, ...) input activations.
        num_microbatches: microbatch count M (batch % M == 0).
        mesh: the device mesh (must contain ``axis_name``).
        seq_axis/seq_dim: when sequence parallelism is active inside the
            stages, the mesh axis and the *activation* dim to shard over it.
            The shard_map then goes manual over ``{pipe, seq}`` in ONE
            region (Shardy rejects a seq-manual shard_map nested inside the
            pipe-manual one: AD residual shardings would put the manual seq
            axis after the free pipe axis); the stage's attention hook
            detects the already-manual seq axis and runs its ring/all_to_all
            collectives directly.
        schedule: ``"shift"`` (pipelined, default) or ``"sequential"``
            (the unpipelined control arm — same stage placement, one
            microbatch in flight; bitwise-pinned against shift).
    Returns: (batch, ...) outputs of the final stage.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r}; one of "
                         f"{SCHEDULES}")
    if schedule == "shift-noskip":
        schedule = "shift"
        if skip_idle is None:
            skip_idle = False
    if schedule == "1f1b":
        # 1F1B's memory contract on the GSPMD shifting scan: the tick
        # order is shift's (forward schedule identical, so the loss is
        # bitwise-pinned against shift AND sequential), but the stage
        # body is rematerialized in backward — the scan saves only the
        # stage-boundary carry, capping the resident activation hold at
        # the schedule's min(S, M) in-flight depth instead of GPipe's
        # all-M retention.
        schedule = "shift"
        stage_fn = jax.checkpoint(stage_fn)
    b = x.shape[0]
    if b % num_microbatches != 0:
        raise ValueError(f"batch {b} not divisible by microbatches "
                         f"{num_microbatches}")
    if axis_name not in mesh.shape:
        raise ValueError(f"mesh {dict(mesh.shape)} has no '{axis_name}' axis; "
                         f"pipeline_apply needs it (add it to mesh_axes)")
    p_size = mesh.shape[axis_name]
    for path, leaf in jax.tree_util.tree_flatten_with_path(stage_params)[0]:
        lead = getattr(leaf, "shape", (None,))[0] if getattr(leaf, "ndim", 0) else None
        if lead != p_size:
            raise ValueError(
                f"stage_params leaf {jax.tree_util.keystr(path)} has leading "
                f"dim {lead}, but the '{axis_name}' mesh axis has size "
                f"{p_size}; each device runs exactly one stage, so the stage "
                f"count must equal the pipe-axis size")
    mb = b // num_microbatches
    x_micro = x.reshape((num_microbatches, mb) + x.shape[1:])
    sharded_commit = num_microbatches % p_size == 0 and p_size > 1

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stage_params)
    iota = jnp.arange(p_size, dtype=jnp.int32)
    manual = {axis_name}
    xspec = [None] * x_micro.ndim
    seq_manual = seq_axis is not None and \
        dict(mesh.shape).get(seq_axis, 1) > 1
    if seq_manual:
        # Activation dim d sits at x_micro dim d+1 ((M, mb) replaced (batch,)).
        xspec[seq_dim + 1] = seq_axis
        manual.add(seq_axis)
    else:
        # Full-manual upgrade: take the data axis manual too (microbatch
        # rows on the mb dim) when it exists, divides, and is not already
        # manual in an enclosing region (explicit-path nesting).  Stage
        # compute is row-independent, so semantics are unchanged — the
        # gradient psum over ``data`` moves from GSPMD into shard_map's
        # transpose.
        am_probe = jax.sharding.get_abstract_mesh()
        enclosing_manual = set(getattr(am_probe, "manual_axes", ()) or ()) \
            if am_probe is not None else set()
        n_data = dict(mesh.shape).get(const.MESH_AXIS_DATA, 1)
        if n_data > 1 and mb % n_data == 0 and \
                const.MESH_AXIS_DATA not in enclosing_manual:
            xspec[1] = const.MESH_AXIS_DATA
            manual.add(const.MESH_AXIS_DATA)
    ospec = P(*([axis_name] + xspec[1:])) if sharded_commit else P(*xspec)
    xspec = P(*xspec)
    # ``skip_idle=None`` = auto (resolve_skip_idle); tests force it
    # on/off to measure the garbage-compute saving.
    if skip_idle is None:
        skip_idle = resolve_skip_idle(seq_manual=seq_manual)
        if not skip_idle and seq_manual:
            from autodist_tpu.utils import logging
            m_ = num_microbatches
            slots = num_schedule_steps(p_size, m_, sharded_commit, schedule)
            logging.warning(
                "pipeline x sequence-parallel composition disables the "
                "fill/drain skip (lax.cond cannot wrap the stage's "
                "manual seq-axis collectives): each rank executes %d "
                "schedule slots for %d real microbatches (+%d%% stage "
                "compute). Raise num_microbatches to amortize — "
                "M >= 4*P keeps the overhead under ~20%%.",
                slots, m_, round(100 * (slots - m_) / m_))
    am = jax.sharding.get_abstract_mesh()
    use = am if (am is not None and am.shape and
                 dict(am.shape) == dict(mesh.shape)) else mesh
    with jax.named_scope("pipeline"):
        inner = jax.shard_map(
            lambda sp, xm, il: _pipeline_local(sp, stage_fn, xm, axis_name,
                                               p_size, il[0], sharded_commit,
                                               skip_idle=skip_idle,
                                               schedule=schedule),
            mesh=use, in_specs=(pspec, xspec, P(axis_name)), out_specs=ospec,
            axis_names=manual, check_vma=False)
        out = inner(stage_params, x_micro, iota)
    if sharded_commit:
        # Rank r holds microbatches m ≡ r (mod P) in slot m // P; the global
        # concat order is (rank, slot) — restore microbatch order with a
        # pure layout transpose (GSPMD moves data only if a consumer asks).
        n_local = num_microbatches // p_size
        out = out.reshape((p_size, n_local) + out.shape[1:]) \
                 .swapaxes(0, 1) \
                 .reshape((num_microbatches,) + out.shape[1:])
    return out.reshape((b,) + out.shape[2:])
