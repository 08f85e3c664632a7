"""Trace-time parallel context: how strategies reach inside a model.

The reference's contract is "single-device user code in, distributed out"
(``/root/reference/docs/design/architecture.rst:1-95``) — it edits the
TF GraphDef to get there.  A jaxpr cannot be usefully edited the same way,
so the TPU-native equivalent is a *dispatch context*: the Runner activates
a :class:`ParallelContext` (built from the strategy proto's GraphConfig)
around the user's loss function **at trace time**, and the framework's
model-level ops — the attention resolver (``models/transformer.py``),
:func:`autodist_tpu.ops.scan_blocks` and the layer loop's
:func:`layer_boundary` — consult it to pick the distributed lowering.  With
no context (or a trivial mesh) the same ops keep their single-device
semantics, so models remain runnable as plain JAX programs.
"""
import contextlib
import contextvars

from autodist_tpu import const

_var = contextvars.ContextVar("autodist_tpu_parallel_ctx", default=None)


class ParallelContext:
    """What the strategy decided about intra-program parallelism.

    Attributes:
        mesh: the device mesh the program runs on.
        seq_attn: "" | "ring" | "ulysses" — sequence-parallel attention
            implementation (GraphConfig.seq_attn).
        pipeline_microbatches: GPipe microbatch count M; >0 activates the
            pipeline lowering of ``scan_blocks`` (GraphConfig.pipeline_microbatches).
        pipeline_schedule: ``"shift"`` (pipelined, default),
            ``"sequential"`` (the bitwise unpipelined control arm), or
            ``"1f1b"`` (shift's tick order with rematerialized stage
            bodies — the min(S, M) activation hold); resolved from
            ``AUTODIST_PIPELINE_SCHEDULE`` when not given
            (docs/pipelining.md).
        op_shardings: ``{scope path: parsed PartitionSpec tuple}`` — the
            automap searcher's per-op activation constraints
            (GraphConfig.op_shardings); the Runner's gspmd path injects
            them at trace time via ``with_sharding_constraint``.
    """

    def __init__(self, mesh, seq_attn="", pipeline_microbatches=0,
                 act_seq_dim=1, op_shardings=None, pipeline_schedule=None):
        self.mesh = mesh
        self.seq_attn = seq_attn
        self.pipeline_microbatches = pipeline_microbatches
        self.pipeline_schedule = (pipeline_schedule or
                                  const.ENV.AUTODIST_PIPELINE_SCHEDULE.val or
                                  "shift")
        self.op_shardings = dict(op_shardings or {})
        # Which activation dim is the sequence: (batch, seq, hidden) is the
        # framework-wide convention (models/, ring_attention, remapper).
        self.act_seq_dim = act_seq_dim
        # True once the model actually took the strategy's attention hook
        # (resolve_attn returned it during this trace).  scan_blocks only
        # seq-shards pipelined activations in that case: a model wired with
        # an explicit attn_fn never sees the hook, and sharding its
        # sequence dim would silently compute block-diagonal attention.
        self.attn_hook_in_use = False
        self._attn_cache = {}
        # The explicit step's account of its ``fsdp`` leaves for the trace
        # in progress (``kernel/synchronization/grad_scatter.GradScatter``),
        # which :func:`layer_boundary` hands the layers ahead; None on every
        # other lowering.
        self.grad_scatter = None

    def attn_fn(self, causal):
        """The strategy's attention hook, or None for default attention.

        Causality must come from the model (its config knows; a mask tensor
        alone cannot be trusted to mean plain causality), which is why the
        resolver takes an explicit flag instead of inspecting masks.
        """
        if not self.seq_attn or self.mesh is None:
            return None
        if dict(self.mesh.shape).get(const.MESH_AXIS_SEQ, 1) <= 1:
            return None  # no seq axis on this mesh: dense is already right
        key = (self.seq_attn, bool(causal))
        fn = self._attn_cache.get(key)
        if fn is None:
            from autodist_tpu.parallel.ring_attention import (
                make_ring_attn_fn, make_ulysses_attn_fn)
            make = {"ring": make_ring_attn_fn,
                    "ulysses": make_ulysses_attn_fn}.get(self.seq_attn)
            if make is None:
                raise ValueError(f"unknown seq_attn {self.seq_attn!r} "
                                 f"(expected 'ring' or 'ulysses')")
            fn = make(self.mesh, causal=causal)
            self._attn_cache[key] = fn
        self.attn_hook_in_use = True
        return fn


def current():
    """The active ParallelContext, or None outside a Runner trace."""
    return _var.get()


@contextlib.contextmanager
def use(ctx):
    # A context is cached per DistributedProgram and may wrap many traces;
    # the hook-use flag must describe *this* trace, not any earlier one,
    # or scan_blocks would seq-shard activations of a model that never
    # took the attention hook (block-diagonal attention, silently).
    ctx.attn_hook_in_use = False
    token = _var.set(ctx)
    try:
        yield ctx
    finally:
        _var.reset(token)


def resolve_attn(causal=False):
    """Strategy-provided ``attn_fn(q, k, v, mask)`` or None (use default)."""
    ctx = current()
    return ctx.attn_fn(causal) if ctx is not None else None


def layer_boundary(params_ahead, x):
    """The op an unrolled layer loop calls where a layer (or a half of
    one) begins, with parameters it will use further on and the activation
    that enters here: ``(params_ahead, x)``, unchanged in value.

    On the Runner's explicit step with ``fsdp`` variables and a data axis
    above 1, the leaves of ``params_ahead`` whose bytes make bandwidth their
    cost are gathered here, and backward their gradients' reduce-scatter
    (asynchronous permutes, ``grad_scatter.exchange_scatter``) must be done
    before ``x``'s cotangent goes on below this point: the scatter is in
    flight during the backward pass between the parameters' use and here.
    With no such context (one chip, GSPMD, plain JAX) the arguments come
    back untouched at trace time: no ``custom_vjp``, no barrier, the
    caller's jaxpr.
    """
    ctx = current()
    if ctx is None or ctx.grad_scatter is None:
        return params_ahead, x
    return ctx.grad_scatter.boundary(params_ahead, x)
