"""Trace-time injection of automap's per-op sharding constraints.

The strategy artifact carries ``GraphConfig.op_shardings`` — scope path
-> activation ``PartitionSpec`` — but the user's loss function is plain
single-device JAX with ``jax.named_scope`` annotations and no sharding
calls.  This module closes that gap on the GSPMD path: the Runner wraps
the loss in :func:`wrap_with_constraints`, which traces it once, finds
the LAST equation of each constrained scope (the scope's exit
activation), and replays the jaxpr equation-by-equation inside the
surrounding trace with ``jax.lax.with_sharding_constraint`` applied at
those anchor points — per-op constraints injected without the model
ever naming a mesh axis (the GSPMD construction of arXiv:2105.04663;
the reference's strategy proto anticipated exactly this op partitioning
"in the future").

Fail-open by design: any anchor whose rank/divisibility does not match
is skipped, and any replay failure falls back to calling the original
loss (a constraint is a performance hint, never a semantics change).
"""
import jax
from jax.sharding import NamedSharding, PartitionSpec

from autodist_tpu.automap.plan import text_to_spec
from autodist_tpu.graph_item import scope_path
from autodist_tpu.utils import logging


def parse_op_shardings(raw):
    """``GraphConfig.op_shardings`` (scope -> serialized spec) -> a plain
    ``{scope: tuple}`` dict of parsed spec entries."""
    return {str(k): text_to_spec(v) for k, v in dict(raw or {}).items()}


def _axis_size(mesh, name):
    try:
        return dict(mesh.shape).get(name, 0)
    except Exception:  # noqa: BLE001
        return 0


def _constrainable(aval, spec, mesh):
    """A spec applies only when ranks match, every named axis exists on
    the mesh, and every sharded dim divides evenly (an uneven activation
    constraint would force GSPMD padding semantics the plan never
    priced)."""
    shape = getattr(aval, "shape", None)
    if shape is None or len(shape) != len(spec):
        return False
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for ax in axes:
            size = _axis_size(mesh, ax)
            if size < 1:
                return False
            total *= size
        if total > 1 and dim % total:
            return False
    return True


def _anchor_eqns(jaxpr, op_shardings):
    """{eqn index: spec} — the last top-level equation inside each
    constrained scope.  Sub-scopes count toward their parents ("
    layer0/mlp/..." anchors "layer0/mlp"), matching how the walker's
    scope keys were recorded."""
    last = {}
    for i, eqn in enumerate(jaxpr.eqns):
        try:
            scope = scope_path(getattr(getattr(eqn, "source_info", None),
                                       "name_stack", None))
        except Exception:  # noqa: BLE001 - unreadable stacks anchor nothing
            continue
        if not scope:
            continue
        for key in op_shardings:
            if scope == key or scope.startswith(key + "/"):
                last[key] = i
    return {i: op_shardings[key] for key, i in last.items()}


def _replay(closed, args, anchors, mesh):
    """Evaluate a closed jaxpr under the ambient trace, constraining the
    outputs of anchor equations (the structure of ``core.eval_jaxpr``
    with a constraint hook)."""
    jaxpr = closed.jaxpr
    env = {}

    def read(v):
        return v.val if isinstance(v, jax.core.Literal) else env[v]

    def write(v, val):
        env[v] = val

    for v, c in zip(jaxpr.constvars, closed.consts):
        write(v, c)
    for v, a in zip(jaxpr.invars, args):
        write(v, a)
    for i, eqn in enumerate(jaxpr.eqns):
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        vals = [read(v) for v in eqn.invars]
        ans = eqn.primitive.bind(*subfuns, *vals, **bind_params)
        outs = list(ans) if eqn.primitive.multiple_results else [ans]
        spec = anchors.get(i)
        if spec is not None:
            outs = [
                jax.lax.with_sharding_constraint(
                    o, NamedSharding(mesh, PartitionSpec(*spec)))
                if _constrainable(getattr(o, "aval", o), spec, mesh) else o
                for o in outs]
        for v, o in zip(eqn.outvars, outs):
            write(v, o)
    return [read(v) for v in jaxpr.outvars]


def wrap_with_constraints(loss_fn, op_shardings, mesh):
    """Return a loss fn that computes the same values with the artifact's
    per-op sharding constraints anchored at scope exits.

    ``op_shardings`` is the parsed ``{scope: spec tuple}`` map.  Returns
    ``loss_fn`` unchanged when there is nothing to inject or no mesh.
    """
    if not op_shardings or mesh is None:
        return loss_fn

    def constrained(params, batch):
        try:
            closed = jax.make_jaxpr(loss_fn)(params, batch)
            anchors = _anchor_eqns(closed.jaxpr, op_shardings)
            if not anchors:
                return loss_fn(params, batch)
            args = jax.tree_util.tree_leaves((params, batch))
            out_flat = _replay(closed, args, anchors, mesh)
            out_shape = jax.eval_shape(loss_fn, params, batch)
            treedef = jax.tree_util.tree_structure(out_shape)
            return jax.tree_util.tree_unflatten(treedef, out_flat)
        except Exception as e:  # noqa: BLE001 - constraints are hints
            logging.warning(
                "automap: per-op constraint injection skipped "
                "(replay failed: %s)", e)
            return loss_fn(params, batch)
    return constrained
