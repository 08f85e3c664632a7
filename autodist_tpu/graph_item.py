"""GraphItem: the captured-training-program IR.

Capability parity with the reference's ``GraphItem``
(``/root/reference/autodist/graph_item.py:217-473``), redesigned for JAX:

* The reference wraps an opaque ``tf.Graph`` and recovers metadata from it —
  gradient→target pairs, variable ``Info``, captured optimizer ctor args —
  because TF1 graphs are the program.  In JAX the program is a traceable
  function, so the GraphItem holds the pieces directly: a loss function (or a
  full train step), an optax optimizer, the parameter pytree, and derived
  per-variable metadata (shape/dtype/size/trainable/sparse-access).
* ``var_op_name_to_grad_info`` parity = variable metadata here; gradients are
  positional (``jax.grad`` returns a pytree congruent with params), so no name
  matching is needed.
* Sparse-gradient detection (the reference's ``IndexedSlices`` routing,
  ``graph_item.py:319-339``) is done by inspecting the traced jaxpr for
  embedding-style ``gather`` reads of a parameter leaf.
* Serialization (``graph_item.py:419-473``) covers the metadata + jaxpr text;
  the function itself is re-traced on each process from the (identical) user
  program, exactly as every reference worker re-runs the user script.
"""
import functools
import re

import numpy as np
import jax
import jax.numpy as jnp
from jax.tree_util import tree_flatten_with_path, tree_map

from autodist_tpu.proto import graphitem_pb2
from autodist_tpu.utils import logging


def path_to_name(path):
    """Render a jax key path as a '/'-joined logical variable name."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


class TensorSpec:
    """Shape/dtype spec; dim value ``None`` marks the polymorphic batch dim."""

    def __init__(self, shape, dtype, name=""):
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        self.name = name

    def __repr__(self):
        return f"TensorSpec({self.name}, {self.shape}, {self.dtype})"


class VariableItem:
    """Per-variable metadata consumed by strategy builders."""

    def __init__(self, name, shape, dtype, trainable=True, sparse_access=False):
        self.name = name
        self.shape = tuple(int(s) for s in shape)
        self.dtype = jnp.dtype(dtype)
        self.trainable = trainable
        self.sparse_access = sparse_access

    @property
    def size_bytes(self):
        return int(np.prod(self.shape, dtype=np.int64)) * self.dtype.itemsize \
            if self.shape else self.dtype.itemsize

    @property
    def num_elements(self):
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1

    def __repr__(self):
        return (f"VariableItem({self.name}, {self.shape}, {self.dtype}, "
                f"sparse={self.sparse_access})")


#: The reserved entry of a loss's ``aux``: ``{variable name: its value after
#: this step}`` (:meth:`GraphItem.capture`).
STATE_UPDATES = "state_updates"


def _sub_jaxpr(eqn):
    """The one jaxpr a call-like equation runs on its own operands (``jit``,
    ``checkpoint``, ``custom_jvp`` / ``custom_vjp`` calls), else None."""
    # Closed jaxprs (:func:`_sub_jaxprs`) and plain ones (``checkpoint``).
    found = list(_sub_jaxprs(eqn)) + [v for v in eqn.params.values()
                                      if hasattr(v, "eqns")]
    if len(found) != 1:
        return None
    inner = found[0]
    return inner if len(inner.invars) == len(eqn.invars) \
        and len(inner.outvars) == len(eqn.outvars) else None


def _differentiable_reach(jaxpr, sources):
    """Whether each outvar of ``jaxpr`` depends on one of the invars flagged
    in ``sources`` through operations a gradient passes: the dependence ends
    at ``stop_gradient`` and at every value that is not floating point (an
    index, a count, a comparison).  An equation with several jaxprs of its
    own (``scan``, ``while``, ``cond``) is not entered: its floating-point
    outputs count as reached (docs/usage/state-updates.md)."""
    reached = {id(v) for v, flagged in zip(jaxpr.invars, sources) if flagged}

    def hit(v):      # a literal is never in the set
        return id(v) in reached

    for eqn in jaxpr.eqns:
        flags = [hit(v) for v in eqn.invars]
        if not any(flags) or eqn.primitive.name == "stop_gradient":
            continue
        inner = _sub_jaxpr(eqn)
        outs = _differentiable_reach(inner, flags) if inner is not None \
            else [True] * len(eqn.outvars)
        reached.update(
            id(v) for v, out in zip(eqn.outvars, outs)
            if out and jnp.issubdtype(v.aval.dtype, jnp.inexact))
    return [hit(v) for v in jaxpr.outvars]


def _check_state_updates(traced, variables):
    """The names under ``aux["state_updates"]`` of the traced loss, checked:
    each is a variable, its value has the variable's shape and dtype, and
    no gradient of the loss reaches the variable (the optimizer's update of
    it is then zero and the step's write is the only change)."""
    closed, out = traced
    aux = out[1] if isinstance(out, (tuple, list)) and len(out) == 2 else None
    if not isinstance(aux, dict) or STATE_UPDATES not in aux:
        return ()
    updates = aux[STATE_UPDATES]
    by_name = {v.name: (i, v) for i, v in enumerate(variables)}
    n_in = len(closed.jaxpr.invars)
    for name, value in updates.items():
        if name not in by_name:
            raise ValueError(
                f"aux['{STATE_UPDATES}'] names {name!r}, which is no "
                f"variable of the captured parameters")
        index, var = by_name[name]
        if tuple(value.shape) != var.shape or value.dtype != var.dtype:
            raise ValueError(
                f"aux['{STATE_UPDATES}'][{name!r}] is {value.dtype}"
                f"{tuple(value.shape)}; the variable is {var.dtype}"
                f"{var.shape}")
        if _differentiable_reach(closed.jaxpr,
                                 [i == index for i in range(n_in)])[0]:
            raise ValueError(
                f"aux['{STATE_UPDATES}'] sets {name!r}, but the loss also "
                f"has a gradient with respect to it: a variable the step "
                f"writes must enter the loss through jax.lax.stop_gradient "
                f"(or through values with no gradient, such as an index)")
    return tuple(updates)


def _trace_loss(loss_fn, params, batch_struct):
    """``(closed jaxpr, output structs)`` of ``loss_fn`` on abstract params
    and batch, or None where it does not trace (capture's reading of the
    loss is best-effort: the Runner's own trace is the one that must
    succeed)."""
    try:
        return jax.make_jaxpr(loss_fn, return_shape=True)(
            tree_map(lambda l: jax.ShapeDtypeStruct(jnp.shape(l),
                                                    jnp.result_type(l)),
                     params), batch_struct)
    except Exception as e:  # noqa: BLE001 - best-effort
        logging.debug("loss not traced at capture: %s", e)
        return None


def _bf16_compute(loss_fn, aux_output):
    """Mixed-precision policy: bf16 compute, f32 master weights/loss.

    Only f32 leaves are cast (ints/bools/f64 untouched).  The cast sits
    inside the traced program, so under ``value_and_grad`` its VJP casts
    cotangents back to f32 — gradients, optimizer state, and the stored
    parameters never leave f32.
    """
    def down(x):
        return x.astype(jnp.bfloat16) \
            if jnp.result_type(x) == jnp.float32 else x

    def wrapped(params, batch):
        out = loss_fn(tree_map(down, params), tree_map(down, batch))
        if aux_output:
            loss, aux = out
            return (loss.astype(jnp.float32),
                    tree_map(lambda a: a.astype(jnp.float32)
                             if jnp.result_type(a) == jnp.bfloat16 else a,
                             aux))
        return out.astype(jnp.float32)
    return wrapped


def _eqn_flops(eqn):
    """Matmul/conv FLOPs of ONE equation (0.0 for everything else)."""
    name = eqn.primitive.name
    if name == "dot_general":
        out = eqn.outvars[0].aval.shape
        (lc, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        k = 1
        for d in lc:
            k *= lhs[d]
        return 2.0 * float(np.prod(out, dtype=np.float64)) * k
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval.shape
        rhs = eqn.invars[1].aval.shape  # kernel: receptive field * C_in
        kernel_elems = float(np.prod(rhs, dtype=np.float64))
        out_feats = rhs[-1] if rhs else 1
        return 2.0 * float(np.prod(out, dtype=np.float64)) * \
            kernel_elems / max(1, out_feats)
    return 0.0


def _eqn_out_bytes(eqn):
    """Bytes written by one equation's outputs (HBM-traffic proxy)."""
    total = 0.0
    for ov in eqn.outvars:
        aval = getattr(ov, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is None:
            continue
        dt = getattr(aval, "dtype", None)
        itemsize = jnp.dtype(dt).itemsize if dt is not None else 4
        total += float(np.prod(shape, dtype=np.float64)) * itemsize
    return total


def _sub_jaxprs(eqn):
    for p in eqn.params.values():
        sub = getattr(p, "jaxpr", None)
        if sub is not None:
            yield sub
        elif isinstance(p, (list, tuple)):
            for q in p:
                sub = getattr(q, "jaxpr", None)
                if sub is not None:
                    yield sub


def _count_flops(jaxpr):
    """Sum matmul/conv FLOPs over a jaxpr, recursing into sub-jaxprs."""
    total = 0.0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        for sub in _sub_jaxprs(eqn):
            total += _count_flops(sub)
    return total


def _live_set_peak_bytes(jaxpr):
    """Peak live bytes of a linear last-use walk over ``jaxpr.eqns``.

    Every equation output stays live from the equation that produces it
    until the last equation that consumes it retires (jaxpr outputs stay
    live through the end).  Jaxpr *inputs* — parameters and the batch —
    are deliberately excluded: the memory ledger charges those to its
    params/staging classes, and counting them here would double-book.

    A jaxpr whose body is one giant call (``jit``/``pjit`` wrapping) is
    unwrapped first so the scan sees the real equation sequence.
    """
    # Descend through single-equation wrapper jaxprs (jit/pjit/closed
    # call frames) until a multi-equation body — or a true one-eqn
    # program — is reached.
    seen = 0
    while len(jaxpr.eqns) == 1 and seen < 16:
        subs = list(_sub_jaxprs(jaxpr.eqns[0]))
        if not subs:
            break
        jaxpr = subs[0]
        seen += 1

    eqns = jaxpr.eqns
    n = len(eqns)
    produced_at = {}
    sizes = {}
    for i, eqn in enumerate(eqns):
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is None:
                continue
            dt = getattr(aval, "dtype", None)
            itemsize = jnp.dtype(dt).itemsize if dt is not None else 4
            produced_at[id(ov)] = i
            sizes[id(ov)] = float(np.prod(shape, dtype=np.float64)) * itemsize
    last_use = dict(produced_at)
    for i, eqn in enumerate(eqns):
        for iv in eqn.invars:
            if id(iv) in produced_at:
                last_use[id(iv)] = max(last_use[id(iv)], i)
    # Jaxpr outputs (the loss, residuals threaded out) survive the whole
    # program — pin them past the final equation.
    for ov in jaxpr.outvars:
        if id(ov) in produced_at:
            last_use[id(ov)] = n
    frees = {}
    for vid, idx in last_use.items():
        frees.setdefault(idx, []).append(vid)
    live = 0.0
    peak = 0.0
    for i, eqn in enumerate(eqns):
        for ov in eqn.outvars:
            live += sizes.get(id(ov), 0.0)
        if live > peak:
            peak = live
        for vid in frees.get(i, ()):
            live -= sizes.get(vid, 0.0)
    return peak


# Scope bucket for equations that carry no usable `jax.named_scope`
# provenance (empty/absent/unreadable name stacks).  The per-layer
# profiler and the automap walker both require EVERY traced equation to
# land in some bucket — costs may be unattributed, never dropped.
UNATTRIBUTED = "(unattributed)"

# Transform frames the name stack wraps around user scopes: `jvp(layer0)`,
# `transpose(jvp(layer0))`, ... — the scope is the payload.  `jit(...)` /
# `pjit(...)` frames carry function names, not scopes, and are dropped.
_SCOPE_WRAP_RE = re.compile(
    r"\b(?:jvp|vjp|transpose|vmap|pmap|remat|checkpoint|custom_jvp|"
    r"custom_vjp|scan|while|cond)\(([^()]*)\)")
# The frames a loop or a conditional lowers its body's instructions under
# (``.../moe/while/body/dispatch/...``, ``.../cond/branch_1_fun/...``, a
# differentiated scan's ``.../pass/while/body/closed_call/layer0/...``):
# machinery between the user's scopes.  The loop or conditional itself
# (``.../moe/while``) keeps its name.
_BODY_FRAME_RE = re.compile(
    r"(?<![^/])(?:while/(?:body|cond)|cond/branch_\d+_fun|closed_call)/")
# ``jax.checkpoint``'s frames: what the backward pass computes of its
# function carries ``checkpoint/``, what it computes again
# ``checkpoint/rematted_computation/``, both behind a second copy of the
# scopes the call stood in (``transpose(jvp(layer0))/jvp(layer0)/checkpoint/
# gdn/...``, the call itself ``.../jvp(layer0)/remat2``); the instruction
# belongs where the call stood.
_REMAT_FRAME_RE = re.compile(
    r"(transpose\(jvp\(([^()]*)\)\)/)jvp\(\2\)/"
    r"(?:checkpoint/(?:rematted_computation/)?|(?=remat2$))")
# The same inside a differentiated loop's body, where the transform frames
# are gone and the second copy of the scopes stands bare
# (``pass/layer0/mlp/layer0/mlp/checkpoint/rematted_computation/...``).
_BARE_REMAT_FRAME_RE = re.compile(
    r"(?<![^/])((?:[^/()]+/)+)\1checkpoint/(?:rematted_computation/)?")


def scope_path(name_stack_text):
    """Normalize a jaxpr name-stack / HLO ``op_name`` into the user's
    ``jax.named_scope`` path (``"layer0/attn"``), dropping jit frames and
    unwrapping autodiff/batching wrappers.  Returns ``""`` when no user
    scope survives — the profiler's *unattributed* signal."""
    if not name_stack_text:
        return ""
    # Unwrap transform frames BEFORE splitting: a scope may itself
    # contain "/" ("stage0/block1"), and the wrapper encloses it whole
    # ("transpose(jvp(stage0/block1))").  Innermost-out, to fixpoint.
    try:
        text = str(name_stack_text)
    except Exception:  # noqa: BLE001 - an unprintable stack is unattributed
        return ""
    prev, text = None, _REMAT_FRAME_RE.sub(r"\1", text)
    while prev != text:
        prev = text
        text = _SCOPE_WRAP_RE.sub(r"\1", text)
    text = _BARE_REMAT_FRAME_RE.sub(r"\1", _BODY_FRAME_RE.sub("", text))
    segments = []
    for seg in text.split("/"):
        seg = seg.strip()
        # jit(f)/pjit(f) frames (or anything still carrying a call frame)
        # are machinery, not user scopes; so is the bare "shard_map" frame
        # the explicit path's body is traced under.
        if not seg or "(" in seg or ")" in seg or seg == "shard_map":
            continue
        segments.append(seg)
    return "/".join(segments)


# A looped model (``TransformerConfig.loops``) runs its stack under the scope
# ``pass`` (one scan over the passes) and each pass's head and gate under
# ``pass<t>``.
_PASS_RE = re.compile(r"^pass\d*(?:/|$)")


def strip_pass(scope):
    """A :func:`scope_path` less its leading ``pass`` or ``pass<t>``: a
    variable's scope (``layer0/attn``) names one place a pass in a looped
    program, and what joins equations to variables or folds them into a
    plain model's rows reads the path without the pass."""
    return _PASS_RE.sub("", scope or "")


class GraphItem:
    """Captured training program + metadata.

    Construct via :meth:`capture`. ``loss_fn(params, batch) -> scalar`` is the
    single-device user program; ``optimizer`` is an optax
    ``GradientTransformation`` (the interposition point replacing the
    reference's optimizer monkey-patching, ``/root/reference/autodist/patch.py:79-90``).
    """

    def __init__(self, loss_fn, params, optimizer=None, batch_spec=None,
                 variables=None, optimizer_name="", aux_output=False,
                 batch_struct=None, precision=None):
        self.loss_fn = loss_fn
        self.params = params
        self.optimizer = optimizer
        self.optimizer_name = optimizer_name
        self.batch_spec = batch_spec
        self.batch_struct = batch_struct  # ShapeDtypeStruct pytree of the example batch
        self.variables = variables or []
        self.aux_output = aux_output  # loss_fn returns (loss, aux)
        # Names of the variables aux["state_updates"] sets (capture).
        self.state_updates = ()
        self.precision = precision  # None (full) | "bf16" (mixed compute)
        self._jaxpr_text = None
        self._flops_estimate = None
        self._op_provenance = None
        self._activation_live_bytes = None

    # -- capture -------------------------------------------------------------

    @classmethod
    def capture(cls, loss_fn, params, optimizer=None, example_batch=None,
                sparse_params=(), non_trainable=(), aux_output=None,
                precision=None):
        """Build a GraphItem from a single-device loss function.

        Args:
            loss_fn: ``(params, batch) -> loss`` (or ``(loss, aux)`` with
                ``aux_output=True``).
            aux_output: whether ``loss_fn`` returns ``(loss, aux)``.  Left
                None it is read off the loss as traced on
                ``example_batch`` (False without one).  Given, it must
                agree with that trace: a loss that returns a pair under
                ``aux_output=False``, or a bare loss under True, raises.
                ``aux`` is the caller's, returned by every step as
                ``metrics["aux"]``, but for one reserved entry: a dict
                ``aux["state_updates"]`` maps a variable's name to the
                value the variable takes AFTER this step, same shape and
                dtype, for state that moves by a rule and not by a
                gradient (a router's selection bias, a running
                statistic).  The step writes it after the optimizer's
                update and takes the entry out of ``metrics["aux"]``.
                Capture raises where a name is no variable, a value has
                another shape or dtype, or the loss has a gradient with
                respect to the variable (it must enter the loss through
                ``jax.lax.stop_gradient``); it needs ``example_batch``.
                The GSPMD step and its megastep apply it; the explicit
                ``shard_map`` step raises NotImplementedError
                (docs/usage/state-updates.md).
            params: parameter pytree (arrays or ShapeDtypeStructs).
            optimizer: optax GradientTransformation.
            example_batch: example batch pytree; first dim is treated as the
                polymorphic batch dimension (parity:
                ``/root/reference/autodist/autodist.py:212-214``).
            sparse_params: iterable of name substrings to force-mark as
                sparse-access (in addition to jaxpr-based detection).
            non_trainable: iterable of name substrings marked non-trainable.
            precision: ``"bf16"`` wraps the loss in a mixed-precision
                policy — f32 leaves of params and batch are cast to
                bfloat16 at the loss boundary (so matmuls/convs hit the
                MXU at 2x f32 rate), while master weights, optimizer
                state, gradients (the cast's VJP casts cotangents back
                up), and the loss itself stay f32.  bf16 keeps f32's
                exponent range, so no loss scaling is needed (unlike
                fp16).  Sub-networks needing f32 islands (e.g. a softmax
                over a huge vocab) can cast up inside ``loss_fn``.
        """
        if precision not in (None, "bf16"):
            raise ValueError(f"precision must be None or 'bf16', got "
                             f"{precision!r}")
        leaves, _ = tree_flatten_with_path(params)
        variables = []
        for path, leaf in leaves:
            name = path_to_name(path)
            variables.append(VariableItem(
                name, jnp.shape(leaf), jnp.result_type(leaf),
                trainable=not any(s in name for s in non_trainable)))

        batch_spec = None
        if example_batch is not None:
            bleaves, _ = tree_flatten_with_path(example_batch)
            batch_spec = [TensorSpec(((None,) + tuple(jnp.shape(l))[1:])
                                     if jnp.ndim(l) else (),
                                     jnp.result_type(l), path_to_name(p))
                          for p, l in bleaves]

        batch_struct = traced = None
        if example_batch is not None:
            batch_struct = tree_map(
                lambda l: jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l)),
                example_batch)
            traced = _trace_loss(loss_fn, params, batch_struct)
        if traced is not None:
            returns_pair = isinstance(traced[1], (tuple, list)) \
                and len(traced[1]) == 2
            if aux_output is None:
                aux_output = returns_pair
            elif bool(aux_output) != returns_pair:
                raise ValueError(
                    f"aux_output={aux_output!r}, but loss_fn returns "
                    f"{'a (loss, aux) pair' if returns_pair else 'a bare loss'}"
                    f" on example_batch")
        aux_output = bool(aux_output)
        item = cls(loss_fn, params, optimizer,
                   batch_spec=batch_spec, variables=variables,
                   optimizer_name=getattr(optimizer, "__name__", "") or
                   type(optimizer).__name__ if optimizer is not None else "",
                   aux_output=aux_output, batch_struct=batch_struct,
                   precision=precision)
        if traced is not None:
            item.state_updates = _check_state_updates(traced, variables)
        if example_batch is not None:
            # Detection runs on the UNWRAPPED user program: the bf16 cast
            # would interpose convert_element_type between the param invar
            # and the gather, hiding embedding lookups from the jaxpr scan
            # (and mis-routing them to dense sync under Parallax).
            item._detect_sparse_access(traced)
        for v in item.variables:
            if any(s in v.name for s in sparse_params):
                v.sparse_access = True
        if precision == "bf16":
            item.loss_fn = _bf16_compute(loss_fn, aux_output)
        return item

    def _detect_sparse_access(self, traced):
        """Mark parameters read through `gather` (embedding lookups) as sparse.

        Replaces the reference's IndexedSlices-based sparse routing
        (``/root/reference/autodist/graph_item.py:319-339``): in the traced
        loss (:func:`_trace_loss`; None skips the detection), any parameter
        leaf that is the gathered operand of a ``gather`` primitive gets
        ``sparse_access=True``.
        """
        if traced is None:
            return
        closed = traced[0]
        n_params = len(jax.tree_util.tree_leaves(self.params))
        param_invars = set(map(id, closed.jaxpr.invars[:n_params]))

        gathered = set()

        def scan(jaxpr):
            # Top-level scan: embedding lookups on a parameter appear as a
            # `gather` whose operand is the (unmodified) param input var.
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "gather" and eqn.invars and \
                        id(eqn.invars[0]) in param_invars:
                    gathered.add(id(eqn.invars[0]))

        try:
            scan(closed.jaxpr)
        except Exception as e:  # noqa: BLE001
            logging.debug("sparse-access scan failed: %s", e)
            return
        if gathered:
            for i, (invar, var) in enumerate(zip(closed.jaxpr.invars, self.variables)):
                if id(invar) in gathered:
                    var.sparse_access = True
                    logging.debug("detected sparse access: %s", var.name)

    # -- queries -------------------------------------------------------------

    @property
    def trainable_variables(self):
        return [v for v in self.variables if v.trainable]

    def var_by_name(self, name):
        for v in self.variables:
            if v.name == name:
                return v
        raise KeyError(name)

    @property
    def total_bytes(self):
        return sum(v.size_bytes for v in self.variables)

    def flops_estimate(self):
        """Approximate forward-pass FLOPs of one loss evaluation at the
        captured batch size (tuner cost model input).

        Counts ``dot_general`` (2*M*N*K per batch element) and
        ``conv_general_dilated`` equations in the traced jaxpr, recursing
        into sub-jaxprs (pjit/scan/cond bodies; loop trip counts are not
        multiplied — a deliberate underestimate that cancels in candidate
        *ranking*, where compute is common-mode).  Falls back to the dense
        rule of thumb ``2 * param_elements * batch_size`` when the program
        cannot be traced (metadata-only GraphItems).
        """
        if self._flops_estimate is not None:
            return self._flops_estimate
        batch = self.batch_size or 1
        fallback = 2.0 * sum(v.num_elements for v in self.variables) * batch
        if self.loss_fn is None or self.batch_struct is None:
            self._flops_estimate = fallback
            return fallback
        try:
            closed = jax.make_jaxpr(self.loss_fn)(
                tree_map(lambda l: jax.ShapeDtypeStruct(
                    jnp.shape(l), jnp.result_type(l)), self.params),
                self.batch_struct)
            self._flops_estimate = float(_count_flops(closed.jaxpr)) \
                or fallback
        except Exception as e:  # noqa: BLE001 - estimation is best-effort
            logging.debug("flops estimate failed: %s", e)
            self._flops_estimate = fallback
        return self._flops_estimate

    def activation_live_bytes(self):
        """Peak live activation bytes of one forward evaluation at the
        captured batch size: a linear last-use live-set scan over the
        traced jaxpr — every intermediate stays live from the equation
        that produces it until its final consumer retires, and the scan
        returns the high-water mark (the memory ledger's activation
        class, docs/memory.md).

        Parameter and batch *inputs* are excluded (the ledger's params/
        staging classes own them); only equation outputs count.  ``0.0``
        when the program cannot be traced (metadata-only GraphItems) —
        the ledger then reports no activation class, never guesses.
        """
        if self._activation_live_bytes is not None:
            return self._activation_live_bytes
        if self.loss_fn is None or self.batch_struct is None:
            self._activation_live_bytes = 0.0
            return 0.0
        try:
            closed = jax.make_jaxpr(self.loss_fn)(
                tree_map(lambda l: jax.ShapeDtypeStruct(
                    jnp.shape(l), jnp.result_type(l)), self.params),
                self.batch_struct)
            self._activation_live_bytes = _live_set_peak_bytes(closed.jaxpr)
        except Exception as e:  # noqa: BLE001 - estimation is best-effort
            logging.debug("activation live-set scan failed: %s", e)
            self._activation_live_bytes = 0.0
        return self._activation_live_bytes

    def op_provenance(self):
        """Per-equation provenance of the captured forward program:
        ``[{"eqn", "prim", "scope", "flops", "bytes"}]`` in trace order.

        ``scope`` is the normalized ``jax.named_scope`` path the equation
        ran under (``""`` when the model emitted no scope there) — the
        key the per-layer profiler joins HLO ``op_name`` metadata and
        strategy variables against.  Same FLOP rules as
        :meth:`flops_estimate` (the two share :func:`_eqn_flops`, so the
        per-eqn breakdown sums to the estimate); ``bytes`` is the
        equation's output footprint, the HBM-traffic proxy.  ``[]`` when
        the program cannot be traced (metadata-only GraphItems) — the
        profiler then reports everything unattributed, never guesses.
        """
        if self._op_provenance is not None:
            return self._op_provenance
        if self.loss_fn is None or self.batch_struct is None:
            self._op_provenance = []
            return self._op_provenance
        try:
            closed = jax.make_jaxpr(self.loss_fn)(
                tree_map(lambda l: jax.ShapeDtypeStruct(
                    jnp.shape(l), jnp.result_type(l)), self.params),
                self.batch_struct)
        except Exception as e:  # noqa: BLE001 - provenance is best-effort
            logging.debug("op provenance unavailable: %s", e)
            self._op_provenance = []
            return self._op_provenance
        records = []

        def walk(jaxpr, outer_scope):
            for i, eqn in enumerate(jaxpr.eqns):
                # Provenance hardening: an equation whose name stack is
                # absent, empty, or unreadable still lands in the record
                # (scope "" => the explicit unattributed bucket) — the
                # automap walker depends on every eqn landing somewhere.
                try:
                    stack = getattr(getattr(eqn, "source_info", None),
                                    "name_stack", None)
                    scope = scope_path(stack)
                except Exception:  # noqa: BLE001 - never drop an eqn
                    scope = ""
                if outer_scope:
                    scope = f"{outer_scope}/{scope}" if scope else outer_scope
                records.append({
                    "eqn": len(records), "prim": eqn.primitive.name,
                    "scope": scope, "flops": _eqn_flops(eqn),
                    "bytes": _eqn_out_bytes(eqn)})
                for sub in _sub_jaxprs(eqn):
                    walk(sub, scope)

        walk(closed.jaxpr, "")
        self._op_provenance = records
        return records

    def scope_costs(self):
        """Aggregate :meth:`op_provenance` per scope:
        ``{scope: {"flops", "bytes", "ops"}}`` (the ``""`` key holds
        scope-less equations).  The per-layer profiler's jaxpr-side
        cost input."""
        out = {}
        for rec in self.op_provenance():
            agg = out.setdefault(rec["scope"],
                                 {"flops": 0.0, "bytes": 0.0, "ops": 0})
            agg["flops"] += rec["flops"]
            agg["bytes"] += rec["bytes"]
            agg["ops"] += 1
        return out

    @property
    def batch_size(self):
        """Leading (batch) dim of the captured example batch, or 0."""
        if self.batch_struct is not None:
            for leaf in jax.tree_util.tree_leaves(self.batch_struct):
                shape = getattr(leaf, "shape", ())
                if shape:
                    return int(shape[0])
        for t in (self.batch_spec or []):
            if t.shape:
                return 0 if t.shape[0] is None else int(t.shape[0])
        return 0

    def grad_fn(self):
        """Return ``(params, batch) -> (grads, loss[, aux])`` for the captured loss."""
        return jax.value_and_grad(self.loss_fn, has_aux=self.aux_output)

    @property
    def jaxpr_text(self):
        if self._jaxpr_text is None:
            try:
                spec = tree_map(
                    lambda l: jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l)),
                    self.params)
                self._jaxpr_text = str(jax.make_jaxpr(self.loss_fn)(spec, self.batch_struct))
            except Exception as e:  # noqa: BLE001
                self._jaxpr_text = f"<untraceable: {e}>"
        return self._jaxpr_text

    # -- serialization -------------------------------------------------------

    def to_proto(self, include_jaxpr=False):
        pb = graphitem_pb2.GraphItem(optimizer_name=self.optimizer_name)
        for v in self.variables:
            pb.variables.append(graphitem_pb2.VariableItem(
                name=v.name, shape=list(v.shape), dtype=str(v.dtype),
                trainable=v.trainable, sparse_access=v.sparse_access,
                size_bytes=v.size_bytes))
        for t in (self.batch_spec or []):
            pb.batch_spec.append(graphitem_pb2.TensorSpecProto(
                name=t.name, shape=[-1 if s is None else s for s in t.shape],
                dtype=str(t.dtype)))
        if include_jaxpr:
            pb.jaxpr_text = self.jaxpr_text
        return pb

    def serialize(self, path):
        with open(path, "wb") as f:
            f.write(self.to_proto().SerializeToString())

    @classmethod
    def metadata_from_proto(cls, pb):
        """Rebuild metadata (not the function) from a serialized GraphItem."""
        variables = [VariableItem(v.name, tuple(v.shape), v.dtype,
                                  v.trainable, v.sparse_access)
                     for v in pb.variables]
        batch_spec = [TensorSpec(tuple(None if s == -1 else s for s in t.shape),
                                 t.dtype, t.name) for t in pb.batch_spec]
        return cls(loss_fn=None, params=None, optimizer=None,
                   batch_spec=batch_spec or None, variables=variables,
                   optimizer_name=pb.optimizer_name)

    @classmethod
    def deserialize(cls, path):
        pb = graphitem_pb2.GraphItem()
        with open(path, "rb") as f:
            pb.ParseFromString(f.read())
        return cls.metadata_from_proto(pb)
