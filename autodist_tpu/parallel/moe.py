"""Mixture-of-experts with expert parallelism.

NEW capability vs the reference (EP absent, SURVEY.md §2.3). The production
path (:func:`apply`) is GShard/Switch-style capacity-based dispatch: each
token's top-k experts get the token copied into a fixed-capacity per-expert
buffer ``(E, C, d)`` via a dispatch one-hot, every expert runs its FFN on
only its buffer (≈ T·k·cf/E tokens instead of all T — an E/(k·cf) FLOPs
reduction over dense all-experts compute), and a combine tensor scatters
the results back.  The buffer einsums are MXU matmuls; with expert weights
and buffers carrying the ``expert`` mesh axis on dim 0 (``EXPERT_RULES``),
GSPMD lowers the dispatch/combine contractions to all_to_all-style
exchanges over ICI — the idiomatic SPMD form of expert parallelism.

Tokens overflowing an expert's capacity are dropped for that expert
(standard GShard semantics; the residual connection around the MoE layer
carries them).  ``capacity_factor`` >= E/k guarantees no drops, which the
parity tests use to pin :func:`apply` against :func:`dense_apply` and
:func:`reference_apply` exactly.

Top-k routing uses a load-balancing auxiliary loss (Switch-style):
``aux = E * sum_e(mean_tokens(gate_e) * frac_tokens_routed_e)``.

The second dispatch form (:func:`dropless_apply`) has no capacity: the
``T * k`` assignments are sorted by expert and each expert's rows go through
its matrices in one grouped matrix product (:func:`grouped_product`, the
megablox ``gmm`` Pallas kernel and its own gradient), so every assignment
is computed and none pays for an empty buffer slot.  It is
the form the language-model block uses (``models/transformer.py``,
``ffn="moe"``); :func:`dense_apply` is the oracle of both.

**A share of the experts** (``MoEConfig.held``).  The dropless layer can be
told that it holds only the experts ``held = (first, count)``, one rank's
share of an expert-parallel deployment seen from one chip: the stacked
matrices are ``(count, ...)``, the router keeps all ``num_experts`` outputs
and a token's ``top_k`` are chosen and their weights normalised over all of
them, and the layer computes exactly the part of the result that the held
experts give (plus the shared expert, which every rank holds whole).  What
the absent experts would add is left out and no code stands in for absent
chips.  The share is TOLD, not derived from a mesh: on the ``expert`` mesh
axis of ``EXPERT_RULES`` every chip still computes with all the experts'
matrices gathered or exchanged by GSPMD, while ``held`` is the form in
which the experts stay where they are.  Tokens that travel to the rank
holding their expert, and come back, start from here: the routed parts of
all the shares add up to the whole layer (``tests/test_moe_held.py``), so
what is missing is the exchange of the rows, not the arithmetic.  Such a
layer moves only the rows that meet a held expert, as a rank's receive
buffer would hold them, a chunk at a time and as many chunks as the step's
held count takes (:func:`held_rungs`, :func:`_held_part`).
"""
import functools
import math

import jax
import jax.numpy as jnp

from autodist_tpu import const
from autodist_tpu.models import layers as L
from autodist_tpu.utils import logging

# Sharding rule for ModelParallel-style overlays: expert dim on `expert` axis.
# These shard the stacked matrices of a layer that holds EVERY expert; a layer
# told its share (``MoEConfig.held``) holds ``count`` experts' matrices whole
# on one chip and names no mesh axis (module docstring).
EXPERT_RULES = (
    (r"moe/(up|down|glu)/kernel$", 0),
    (r"moe/gate/kernel$", 1),
)

EXPERT_KINDS = ("gelu", "swiglu")
SCORINGS = ("softmax", "sigmoid")

#: A held layer works through its held rows in chunks of a sixteenth of the
#: step's ``T * k`` assignments (:func:`held_chunk_rows`), unless its
#: configuration names another count (``MoEConfig.held_chunks``).
_HELD_CHUNKS = 16


class MoEConfig:
    def __init__(self, num_experts=8, top_k=2, d_model=64, d_hidden=256,
                 dtype=jnp.float32, capacity_factor=1.25, expert="gelu",
                 norm_topk=True, scoring="softmax", route_scale=1.0,
                 shared=0, select_bias=False, bias_update_rate=0.0,
                 held=None, shared_gate=False, held_chunks=None, groups=None,
                 groups_kept=None):
        if expert not in EXPERT_KINDS:
            raise ValueError(f"expert must be one of {EXPERT_KINDS}, got "
                             f"{expert!r}")
        if scoring not in SCORINGS:
            raise ValueError(f"scoring must be one of {SCORINGS}, got "
                             f"{scoring!r}")
        if shared and expert != "swiglu":
            raise ValueError("shared experts are SwiGLU MLPs: expert must "
                             f"be 'swiglu' with shared={shared!r}, got "
                             f"{expert!r}")
        if shared_gate and not shared:
            raise ValueError("shared_gate weighs the shared experts' output: "
                             "it needs shared >= 1")
        if held is not None:
            first, count = held
            if not (0 <= first and count > 0
                    and first + count <= num_experts):
                raise ValueError(
                    f"held = (first, count) must name consecutive experts "
                    f"among the {num_experts}, got {held!r}")
            held = (int(first), int(count))
        if held_chunks is not None and (held is None or held_chunks < 1):
            raise ValueError(
                f"held_chunks splits a held layer's assignments: it needs "
                f"held and a count >= 1, got {held_chunks!r} with held = "
                f"{held!r}")
        if (groups is None) != (groups_kept is None) or groups is not None \
                and not (scoring == "sigmoid" and num_experts % groups == 0
                         and 0 < groups_kept <= groups
                         and top_k <= groups_kept * (num_experts // groups)):
            raise ValueError(
                f"groups / groups_kept limit a sigmoid router's choice to "
                f"groups_kept of groups equal groups of consecutive experts "
                f"that hold top_k between them, got {groups!r} / "
                f"{groups_kept!r} with {num_experts} experts, top_k {top_k} "
                f"and scoring {scoring!r}")
        self.num_experts = num_experts
        self.top_k = top_k
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.dtype = dtype
        # Per-expert buffer size C = ceil(T * top_k / E * capacity_factor).
        # >= E/top_k guarantees C = T (no token ever dropped).
        self.capacity_factor = capacity_factor
        # "gelu": down(gelu(up x)).  "swiglu": down(silu(glu x) * up x),
        # a third stacked matrix ``glu`` beside ``up`` and ``down``.
        self.expert = expert
        # Whether a token's top-k weights are rescaled to sum to one, or
        # left as the softmax over all experts gave them.
        self.norm_topk = norm_topk
        # What follows is read by :func:`dropless_apply` alone.
        # "softmax" over the experts, or "sigmoid" of each logit on its own
        # (then ``norm_topk`` divides by the chosen scores' sum).
        self.scoring = scoring
        # The chosen experts' weights are multiplied by this, under either
        # scoring (:func:`dropless_apply`; the capacity path has no scale).
        self.route_scale = route_scale
        # Shared experts beside the routed ones (``expert="swiglu"``): one
        # SwiGLU MLP of ``shared * d_hidden`` that every token passes,
        # unweighted, or with ``shared_gate`` times ``sigmoid(w . x)``, one
        # scalar a token from a ``(d_model, 1)`` matrix (Qwen2-MoE's).
        self.shared = shared
        self.shared_gate = shared_gate
        # A per-expert bias added to the scores for the CHOICE of the top_k
        # only (never to the weights; no gradient); after each step it moves
        # by ``bias_update_rate`` towards an even load (``stats``'
        # ``state_updates``, which the Runner writes).
        self.select_bias = select_bias
        self.bias_update_rate = bias_update_rate
        # ``(first, count)``: the consecutive experts this layer holds (the
        # module docstring); None holds all.
        self.held = held
        # A held layer's rows go through its experts in chunks of ``T * k /
        # held_chunks`` rows (:func:`held_chunk_rows`; None: a sixteenth).
        # A chunk that is the layer's even share (``count / num_experts`` of
        # the assignments) leaves the router's draw to decide between one
        # chunk and two, so a deployment whose share is a sixteenth names a
        # count that puts its share well inside a chunk.
        self.held_chunks = (_HELD_CHUNKS if held_chunks is None
                            else int(held_chunks))
        # The group-limited choice (DeepSeek-V3's; sigmoid scoring): the
        # experts are ``groups`` groups of consecutive ids, a group's score
        # is the sum of its two largest score-plus-bias, and a token's
        # ``top_k`` are chosen inside the ``groups_kept`` best groups
        # (:func:`_route_sigmoid` alone reads these); None: no limit.
        self.groups, self.groups_kept = groups, groups_kept

    @property
    def num_held(self):
        return self.num_experts if self.held is None else self.held[1]


def init(key, cfg):
    swiglu = cfg.expert == "swiglu"
    keys = jax.random.split(key, 4 if swiglu else 3)
    up_shape = (cfg.num_held, cfg.d_model, cfg.d_hidden)
    params = {
        "gate": {"kernel": L.glorot(keys[0], (cfg.d_model, cfg.num_experts))},
        "up": {"kernel": L.glorot(keys[1], up_shape, in_axis=-2, out_axis=-1)},
        "down": {"kernel": L.glorot(keys[2], (cfg.num_held, cfg.d_hidden,
                                              cfg.d_model),
                                    in_axis=-2, out_axis=-1)},
    }
    if swiglu:
        params["glu"] = {"kernel": L.glorot(keys[3], up_shape, in_axis=-2,
                                            out_axis=-1)}
    if cfg.shared:
        wide = cfg.shared * cfg.d_hidden
        shared_keys = jax.random.split(jax.random.fold_in(key, 1), 3)
        params["shared"] = {
            "up": L.dense_init(shared_keys[0], cfg.d_model, wide, False),
            "down": L.dense_init(shared_keys[1], wide, cfg.d_model, False),
            "glu": L.dense_init(shared_keys[2], cfg.d_model, wide, False)}
        if cfg.shared_gate:
            params["shared_gate"] = L.dense_init(
                jax.random.fold_in(key, 2), cfg.d_model, 1, False)
    if cfg.select_bias:
        params["bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    return params


def _constrain_expert_sharded(buf):
    """Pin an (E, ...) buffer's leading dim to the `expert` mesh axis.

    GSPMD usually propagates this sharding from the expert weights through
    the buffer einsums on its own, but the expert-parallel FLOPs split is a
    perf contract (tests/test_moe_hlo.py asserts it in compiled HLO), so
    when a strategy mesh with an expert axis is active the constraint is
    explicit rather than left to propagation.  No-op outside a Runner trace
    or on expert-axis-free meshes: the model stays a plain JAX program.
    """
    from autodist_tpu.parallel import context as pctx
    ctx = pctx.current()
    if ctx is None or ctx.mesh is None:
        return buf
    if dict(ctx.mesh.shape).get(const.MESH_AXIS_EXPERT, 1) <= 1:
        return buf
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec(const.MESH_AXIS_EXPERT,
                         *([None] * (buf.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        buf, NamedSharding(ctx.mesh, spec))


def _route(gates, cfg):
    """Top-k routing shared by the dispatch and dense paths.

    gates: (T, E) softmax probabilities.
    Returns (top_vals (T, k), normalized unless ``cfg.norm_topk`` is off,
    top_idx (T, k), aux scalar).
    """
    top_vals, top_idx = jax.lax.top_k(gates, cfg.top_k)
    if cfg.norm_topk:
        top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True),
                                          1e-9)

    # Switch-style load-balancing auxiliary loss (computed pre-drop, the
    # standard formulation: drops depend on buffer order, load balance
    # should not).  Normalize by top_k: the routing indicator sums to top_k
    # per token, so dividing keeps `density` a per-expert token fraction
    # (sums to 1) and the aux scale independent of k.
    routed = jax.nn.one_hot(top_idx, cfg.num_experts,
                            dtype=jnp.float32).sum(-2)          # (T, E)
    density = routed.mean(0) / cfg.top_k
    density_proxy = gates.mean(0)           # mean gate prob per expert
    aux = cfg.num_experts * jnp.sum(density * density_proxy)
    return top_vals, top_idx, aux


def _expert_hidden(params, cfg, x, spec):
    """The experts' hidden activations for the einsum ``spec`` that takes
    ``x`` through a stacked ``(E, d, h)`` matrix."""
    h = jnp.einsum(spec, x, params["up"]["kernel"].astype(cfg.dtype))
    if cfg.expert == "swiglu":
        glu = params["glu"]["kernel"].astype(cfg.dtype)
        return jax.nn.silu(jnp.einsum(spec, x, glu)) * h
    return jax.nn.gelu(h)


def apply(params, cfg, x):
    """x: (..., d_model) -> (moe_out, aux_loss).

    Capacity-based dispatch (the production path): per-expert buffers of
    C = ceil(T*k/E * capacity_factor) tokens; experts compute only their
    buffer.  Dispatch/combine are index-based (gather into the buffer,
    segment-sum back) rather than GShard's (T, E, C) one-hot einsums: the
    one-hot contractions cost 2·T·E·C·d FLOPs each, which at small
    hidden/model ratios rivals the expert compute they were meant to save;
    gathers move the same bytes with no FLOPs and XLA lowers them to
    dynamic-slice loops that stream from HBM.  Buffers and expert weights
    share the leading E dim, so under GSPMD the exchange over the
    ``expert`` mesh axis happens where the gather indices cross shards.
    """
    lead_shape = x.shape[:-1]
    tokens = math.prod(lead_shape)
    flat_x = x.reshape(tokens, cfg.d_model)
    logits = flat_x.astype(jnp.float32) @ \
        params["gate"]["kernel"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    top_vals, top_idx, aux = _route(gates, cfg)

    num_e = cfg.num_experts
    capacity = min(tokens, max(1, math.ceil(
        tokens * cfg.top_k / num_e * cfg.capacity_factor)))
    # Capacity semantics are a numerics contract: at the default
    # capacity_factor=1.25 overflow tokens are DROPPED for that expert
    # (callers wanting the drop-free oracle need capacity_factor >= E/k or
    # dense_apply).  Shapes are static, so this trace-time log fires once
    # per compilation — making drops discoverable without step-loop cost.
    if capacity < tokens:
        logging.info(
            "MoE dispatch: E=%d capacity=%d tokens=%d (top_k=%d, cf=%.2f) — "
            "over-capacity assignments are dropped", num_e, capacity, tokens,
            cfg.top_k, cfg.capacity_factor)

    # k-major assignment order: every token's 1st choice claims buffer
    # slots before any token's 2nd choice (GShard's priority rule), so
    # capacity overflow drops low-priority assignments first.
    idx_flat = top_idx.T.reshape(-1)                            # (k*T,)
    val_flat = top_vals.T.reshape(-1)
    mask = jax.nn.one_hot(idx_flat, num_e, dtype=jnp.int32)
    slot = (jnp.cumsum(mask, axis=0) * mask - mask).sum(-1)     # 0-based
    valid = slot < capacity
    tok_ids = jnp.tile(jnp.arange(tokens, dtype=jnp.int32), cfg.top_k)

    # Token-id buffer (0 = empty): assignment j writes token j%T into
    # expert idx_flat[j]'s slot; invalid assignments write a trash cell.
    # Valid (e, slot) pairs are unique by construction, so no write races.
    flat_ec = jnp.where(valid, idx_flat * capacity + slot, num_e * capacity)
    buf = jnp.zeros((num_e * capacity + 1,), jnp.int32) \
        .at[flat_ec].set(tok_ids + 1)[:num_e * capacity]

    xc = flat_x.astype(cfg.dtype)
    down = params["down"]["kernel"].astype(cfg.dtype)
    occupied = (buf > 0)[:, None]
    expert_in = jnp.where(occupied, xc[jnp.maximum(buf - 1, 0)], 0) \
        .reshape(num_e, capacity, cfg.d_model)
    expert_in = _constrain_expert_sharded(expert_in)
    h = _expert_hidden(params, cfg, expert_in, "ecd,edh->ech")
    expert_out = jnp.einsum("ech,ehd->ecd", h, down) \
        .reshape(num_e * capacity, cfg.d_model)

    # Combine: each assignment gathers its expert's output slot, weighted
    # by the (renormalized) gate; dropped assignments contribute zero.
    y = expert_out[jnp.minimum(flat_ec, num_e * capacity - 1)]
    w = val_flat * valid.astype(jnp.float32)
    out = jax.ops.segment_sum(y.astype(jnp.float32) * w[:, None],
                              tok_ids, num_segments=tokens)
    return out.reshape(lead_shape + (cfg.d_model,)).astype(x.dtype), aux


#: Rows, contraction and columns of one tile of the grouped product; at
#: (65536, 2048) x (64, 2048, 1024) on a v5e the nine products of a step
#: take 25.2 ms with it, 28.4 at 512 x 512 x 1024, 32.5 at 512^3 (which is
#: what ``jax.lax.ragged_dot`` compiles to there: 35.1 ms), and a tile of
#: 1,024 rows or 2,048 deep does not fit the kernel's memory (PERF.md, PR 25).
GMM_TILING = (512, 1024, 1024)

def held_chunk_rows(assignments, chunks=_HELD_CHUNKS):
    """The rows of one chunk of a held layer's buffers: ``assignments /
    chunks`` (a sixteenth unless ``MoEConfig.held_chunks`` says otherwise)
    rounded up to whole row tiles of the grouped product."""
    tile = GMM_TILING[0]
    return min(assignments, -(-assignments // (chunks * tile)) * tile)


def held_rungs(assignments, chunks=_HELD_CHUNKS):
    """The static ladder of a held layer's buffer sizes in rows, ascending:
    the multiples of :func:`held_chunk_rows` up to the first that holds all
    ``assignments``.  A step takes the smallest rung that is at least its
    held count, by running that many chunks."""
    chunk = held_chunk_rows(assignments, chunks)
    return tuple(range(0, assignments + chunk, chunk))


def grouped_product(lhs, rhs, group_sizes):
    """``lhs`` (m, k) rows, sorted into ``len(group_sizes)`` contiguous
    groups, times ``rhs`` (groups, k, n): each group's rows through its own
    matrix, (m, n) in ``lhs``'s dtype with float32 accumulation.

    The megablox kernel (``jax.experimental.pallas.ops.tpu.megablox``) and
    its own gradient (the same kernel on the transposed matrices for the
    rows' gradient, its ``tgmm`` twin for the matrices').  It works a tile
    of rows at a time and visits a tile that straddles two groups once for
    each, so its work follows the rows, not the groups' sizes.  Off the TPU
    the same kernel runs interpreted.  As a Mosaic kernel it cannot be
    partitioned automatically: on a mesh of several devices it must be
    traced inside a region manual over every axis (the Runner's explicit
    path over ``data`` is one), and this raises otherwise.
    """
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from autodist_tpu.parallel import context as pctx
    interpret = jax.default_backend() != "tpu"
    ctx = pctx.current()
    mesh = ctx.mesh if ctx is not None else None
    if not interpret and mesh is not None and mesh.size > 1:
        manual = jax.sharding.get_abstract_mesh().manual_axes
        free = [a for a in mesh.axis_names
                if a not in manual and mesh.shape[a] > 1]
        if free:
            raise NotImplementedError(
                f"the experts' grouped product is a Mosaic kernel and mesh "
                f"{dict(mesh.shape)} leaves {free} automatic; use a "
                f"strategy whose step is manual over every axis (the "
                f"explicit shard_map lowering over 'data')")
    m, k = lhs.shape
    tiling = (math.gcd(m, GMM_TILING[0]), min(k, GMM_TILING[1]),
              min(rhs.shape[2], GMM_TILING[2]))
    return gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, tiling,
               interpret=interpret)


_announced = set()


def _announce(cfg, assignments):
    """Gauges and a ``moe`` event for the dropless layer being traced; the
    event is recorded once a process for each shape traced."""
    from autodist_tpu import observability
    if not observability.enabled():
        return
    registry = observability.registry()
    registry.gauge("moe.experts").set(cfg.num_experts)
    registry.gauge("moe.top_k").set(cfg.top_k)
    registry.gauge("moe.assignments_per_step").set(assignments)
    registry.gauge("moe.experts_held").set(cfg.num_held)
    registry.gauge("moe.softmax_scoring").set(int(cfg.scoring == "softmax"))
    registry.gauge("moe.shared_gate").set(int(cfg.shared_gate))
    if cfg.groups:
        registry.gauge("moe.groups").set(cfg.groups)
        registry.gauge("moe.groups_kept").set(cfg.groups_kept)
    rungs = (held_rungs(assignments, cfg.held_chunks)
             if cfg.held is not None else ())
    registry.gauge("moe.held_buffer_rungs").set(len(rungs))
    registry.gauge("moe.held_chunk_rows").set(rungs[1] if rungs else 0)
    detail = (f"dropless: {assignments} assignments a step over "
              f"{cfg.num_experts} {cfg.expert} experts, {cfg.top_k} a token; "
              f"grouped product megablox gmm tiled {GMM_TILING}, "
              f"({assignments}, {cfg.d_model}) x ({cfg.num_held}, "
              f"{cfg.d_model}, {cfg.d_hidden}) and back")
    if cfg.held is not None or cfg.scoring != "softmax" or cfg.shared:
        first, count = cfg.held or (0, cfg.num_experts)
        detail += (f"; {cfg.scoring} scores, experts {first}-"
                   f"{first + count - 1} held ({count} of {cfg.num_experts}"
                   + (f": held rows in chunks of {rungs[1]} rows, as many "
                      f"as the step's count takes, {len(rungs) - 1} at most"
                      if rungs else "")
                   + f"), {cfg.shared} shared"
                   + (" times the sigmoid of a scalar a token"
                      if cfg.shared_gate else "")
                   + (", selection bias" if cfg.select_bias else "")
                   + (f", chosen inside the {cfg.groups_kept} best of "
                      f"{cfg.groups} groups" if cfg.groups else ""))
    if detail not in _announced:
        _announced.add(detail)
        observability.record_event("moe", detail)


def _sorted_rows(x, order, inverse, top_k):
    """``x`` (T, d) repeated ``top_k`` times a token and put in the sorted
    order of the assignments: ``out[j] = x[order[j] // top_k]``.  Its
    transpose is written as the gather it is (each token sums its own
    ``top_k`` rows), where autodiff would leave a scatter-add."""
    @jax.custom_vjp
    def take(x):
        return x[order // top_k]

    def fwd(x):
        return take(x), None

    def bwd(_, g):
        return (g[inverse].reshape(x.shape[0], top_k, -1)
                .sum(1).astype(g.dtype),)

    take.defvjp(fwd, bwd)
    return take(x)


def _unsorted_rows(y, order, inverse):
    """``y`` (T * k, d) back in the assignments' own order (token-major):
    ``out[a] = y[inverse[a]]``; the transpose is the gather by ``order``."""
    @jax.custom_vjp
    def take(y):
        return y[inverse]

    def fwd(y):
        return take(y), None

    def bwd(_, g):
        return (g[order],)

    take.defvjp(fwd, bwd)
    return take(y)


def _uncovered(sorted_experts, group_sizes):
    """How many of the sorted assignments a grouped product over
    ``group_sizes`` does not take through the expert they chose (float32
    scalar).  The product gives row ``i`` to the first group whose running
    sum of sizes passes ``i``, which is the number of groups that end at or
    before ``i``, and to none past the last group's end (the count is then
    the number of groups, which no assignment chose)."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(sorted_experts.shape[0])
    given = jnp.sum(row[:, None] >= ends[None, :], axis=1)
    return jnp.sum(given != sorted_experts).astype(jnp.float32)


def _route_sigmoid(logits, params, cfg):
    """``(top_vals, top_idx, gates)`` of sigmoid scoring: each expert's score
    is the sigmoid of its own logit; the ``top_k`` are the largest of score
    plus the selection bias (which only chooses: it is not in the weights
    and has no gradient); the weights are the chosen scores, over their sum
    with ``norm_topk``, times ``route_scale``.  ``gates`` are the scores
    over their sum over ALL experts, what the balance term takes for the
    router's probabilities."""
    scores = jax.nn.sigmoid(logits)
    choice = scores + jax.lax.stop_gradient(params["bias"]) \
        if cfg.select_bias else scores
    if cfg.groups:
        with jax.named_scope("groups"):
            choice = _kept_groups(choice, cfg)
    _, top_idx = jax.lax.top_k(choice, cfg.top_k)
    top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
    if cfg.norm_topk:
        top_vals = top_vals / (top_vals.sum(-1, keepdims=True) + 1e-20)
    return (top_vals * cfg.route_scale, top_idx,
            scores / scores.sum(-1, keepdims=True))


def _kept_groups(choice, cfg):
    """``choice`` (T, E), the scores a token chooses by, with every expert
    outside the token's ``cfg.groups_kept`` best groups at ``-inf``: a group
    is ``E / cfg.groups`` consecutive experts, its score the sum of its two
    largest entries, and ties go to the lower group, as ``top_k``'s go to
    the lower expert."""
    tokens, experts = choice.shape
    grouped = choice.reshape(tokens, cfg.groups, experts // cfg.groups)
    best = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0].sum(-1)
    _, kept = jax.lax.top_k(best, cfg.groups_kept)
    keep = jax.nn.one_hot(kept, cfg.groups, dtype=jnp.bool_).any(-2)
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(choice.shape)


def _shared_expert(params, cfg, flat_x, gate=None):
    """The shared experts' MLP over every token, (T, d) float32: unweighted,
    or times ``sigmoid(x . w)`` of ``gate``'s ``(d, 1)`` matrix, the sigmoid
    and the product in float32."""
    xc = flat_x.astype(cfg.dtype)
    hidden = jax.nn.silu(L.dense(params["glu"], xc, cfg.dtype)) \
        * L.dense(params["up"], xc, cfg.dtype)
    out = L.dense(params["down"], hidden, cfg.dtype).astype(jnp.float32)
    if gate is None:
        return out
    return out * jax.nn.sigmoid(L.dense(gate, xc, cfg.dtype)
                                .astype(jnp.float32))


def _experts(cfg, kernel, rows, group_sizes):
    """The experts' MLP of ``rows`` (m, d), sorted into ``group_sizes``'
    contiguous groups: (m, d).  ``kernel(name)`` gives the stacked matrix
    ``up``, ``down`` or ``glu`` in ``cfg.dtype``."""
    def grouped(lhs, name):
        return grouped_product(lhs, kernel(name), group_sizes)
    hidden = jax.nn.silu(grouped(rows, "glu")) * grouped(rows, "up") \
        if cfg.expert == "swiglu" else jax.nn.gelu(grouped(rows, "up"))
    return grouped(hidden, "down")


def _chunk(cfg, chunk, i, x, top_vals, where):
    """Chunk ``i`` of the held rows, ``chunk`` rows of the sorted order:
    ``(the assignment in each row, its token, whether the row is held, its
    weight, the tokens' rows (chunk, d), the experts' group sizes within the
    chunk)``."""
    first = i * chunk
    assignment = jax.lax.dynamic_slice(where["order"], (first,), (chunk,))
    token = assignment // cfg.top_k
    valid = (first + jnp.arange(chunk) < where["held"])[:, None]
    ends = jnp.cumsum(where["group_sizes"])
    inside = jnp.clip(jnp.stack([ends - where["group_sizes"], ends]),
                      first, first + chunk)
    return (assignment, token, valid, top_vals.reshape(-1)[assignment],
            x[token], inside[1] - inside[0])


def _chunk_experts(cfg, valid, group_sizes):
    """The held experts' MLP of a chunk's rows; no product visits the rows
    behind the held ones, which are zero going in and coming out."""
    def run(rows_in, kernels):
        rows_in = jnp.where(valid, rows_in, 0)
        return jnp.where(valid, _experts(cfg, kernels.__getitem__, rows_in,
                                         group_sizes), 0)
    return run


def _cast(cfg, kernels):
    """The stacked matrices in ``cfg.dtype``, as the products take them."""
    with jax.named_scope("experts"):
        return {name: k.astype(cfg.dtype) for name, k in kernels.items()}


# The two loops are inlined ``jit``s: a model's held layers make the same
# call, and every one after the first takes the first's equations from the
# cache (the benchmark's process traces slowly: PERF.md section 7).
@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _held_forward(cfg, chunk, x, top_vals, kernels, where):
    """What the held experts add to each token, (T, d) float32: chunk by
    chunk, each chunk's rows gathered, put through the experts, weighted and
    added to their tokens' rows."""
    kernels = _cast(cfg, kernels)

    def body(i, out):
        with jax.named_scope("dispatch"):
            _, token, valid, weight, rows_in, sizes = _chunk(
                cfg, chunk, i, x, top_vals, where)
        with jax.named_scope("experts"):
            expert_out = _chunk_experts(cfg, valid, sizes)(rows_in, kernels)
        with jax.named_scope("dispatch"):
            return out.at[token].add(
                expert_out.astype(jnp.float32) * weight[:, None])
    return jax.lax.fori_loop(
        0, where["chunks"], body,
        jnp.zeros((x.shape[0], cfg.d_model), jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _held_backward(cfg, chunk, x, top_vals, kernels, where, g):
    """The gradients of ``x``, ``top_vals`` and ``kernels`` for ``g``, that
    of :func:`_held_forward`'s result; each chunk's grouped products are
    computed again, and the sums over the chunks are float32."""
    cast = _cast(cfg, kernels)

    def body(i, sums):
        d_x, d_top_vals, d_kernels = sums
        with jax.named_scope("dispatch"):
            assignment, token, valid, weight, rows_in, sizes = _chunk(
                cfg, chunk, i, x, top_vals, where)
            g_rows = g[token]
        with jax.named_scope("experts"):
            expert_out, pull = jax.vjp(_chunk_experts(cfg, valid, sizes),
                                       rows_in, cast)
            d_rows_in, d_cast = pull(
                (g_rows * weight[:, None]).astype(cfg.dtype))
            d_kernels = jax.tree_util.tree_map(
                lambda total, d: total + d.astype(total.dtype),
                d_kernels, d_cast)
        with jax.named_scope("dispatch"):
            d_weight = jnp.sum(g_rows * expert_out.astype(jnp.float32), 1)
            return (d_x.at[token].add(d_rows_in.astype(jnp.float32)),
                    d_top_vals.at[assignment].add(
                        jnp.where(valid[:, 0], d_weight, 0)),
                    d_kernels)
    d_x, d_top_vals, d_kernels = jax.lax.fori_loop(
        0, where["chunks"], body,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros((top_vals.size,), top_vals.dtype),
         jax.tree_util.tree_map(jnp.zeros_like, kernels)))
    return (d_x.astype(x.dtype), d_top_vals.reshape(top_vals.shape),
            d_kernels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rows(cfg, chunk, x, top_vals, kernels, where):
    """:func:`_held_forward`, with :func:`_held_backward` for its gradient:
    both loop over the same chunks, and nothing is kept between them but
    the inputs."""
    return _held_forward(cfg, chunk, x, top_vals, kernels, where)


def _held_rows_fwd(cfg, chunk, *inputs):
    return _held_forward(cfg, chunk, *inputs), inputs


def _held_rows_bwd(cfg, chunk, inputs, g):
    return _held_backward(cfg, chunk, *inputs, g) + (None,)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def _held_part(params, cfg, flat_x, top_vals, flat_idx, group_sizes, stats):
    """What the held experts add to each token, (T, d) float32, and their
    statistics into ``stats``: :func:`dropless_apply` with ``cfg.held``.

    The held assignments sort first, by held expert and stable in the
    token, and only they are moved: a loop on the device takes them a chunk
    of :func:`held_chunk_rows` rows at a time, as many chunks as hold the
    step's held count (:func:`held_rungs`), and every array between the
    gather of a chunk's token rows and their sum into the tokens' rows has
    a chunk's rows.  At most all ``T * k`` assignments are held, in
    ``cfg.held_chunks`` chunks: a token whose ``top_k`` are all held loses
    none, and the result is exact at every count."""
    (tokens, top_k), (first, count) = top_vals.shape, cfg.held
    chunk = held_chunk_rows(tokens * top_k, cfg.held_chunks)
    with jax.named_scope("dispatch"):
        local = flat_idx - first
        group_sizes = group_sizes[first:first + count]
        held = group_sizes.sum()
        # argsort, keeping the sorted keys the same sort produces.
        sorted_experts, order = jax.lax.sort_key_val(
            jnp.where((local >= 0) & (local < count), local, count),
            jnp.arange(tokens * top_k, dtype=flat_idx.dtype))
        chunks = -(-held // chunk)
        # The last chunk may end behind the assignments: rows never held.
        order = jnp.pad(order, (0, -(tokens * top_k) % chunk))
        stats["dropped"] = _uncovered(sorted_experts, group_sizes)
        stats["held_assignments"] = held.astype(jnp.float32)
        stats["held_buffer_rows"] = (chunks * chunk).astype(jnp.float32)
    out = _held_rows(
        cfg, chunk, flat_x.astype(cfg.dtype), top_vals,
        {name: params[name]["kernel"]
         for name in ("glu", "up", "down") if name in params},
        {"chunks": chunks, "held": held, "order": order,
         "group_sizes": group_sizes})
    stats["held_output_rms"] = jnp.sqrt(jnp.mean(jnp.square(out)))
    return out


def dropless_apply(params, cfg, x):
    """x: (rows, seq, d_model) -> (moe_out, stats); no assignment dropped.

    The router runs in float32; a token's ``top_k`` weights are left as the
    softmax gave them unless ``cfg.norm_topk``, then times
    ``cfg.route_scale`` (``cfg.scoring="sigmoid"``: :func:`_route_sigmoid`,
    with ``cfg.groups`` the choice limited to the best groups under the scope
    ``router/groups``; ``stats["groups_reached"]`` is then the mean number
    of groups a token's choices fall in).
    The ``T * k`` assignments
    are sorted by expert (stable, so an expert's rows keep the tokens'
    order), the tokens' rows gathered in that order, and each of the
    expert matrices applied to its contiguous group of rows by one grouped
    product.  The results go back to the assignments' own order, are
    weighted, and summed per token.  ``cfg.shared`` adds the shared experts'
    MLP of every token (scope ``shared``), with ``cfg.shared_gate`` times the
    sigmoid of one scalar a token (``shared_gate/kernel``).

    With ``cfg.held = (first, count)`` the layer computes the held experts'
    part exactly (:func:`_held_part`): routing, weights and every statistic
    but ``dropped``, ``held_assignments`` and ``held_buffer_rows`` are over
    all ``num_experts``; the assignments sort by held expert with every
    other assignment behind them, and only the held ones are moved: a loop
    on the device takes them a chunk at a time (:func:`held_chunk_rows`: a
    sixteenth of the ``T * k`` assignments, or ``1 / cfg.held_chunks`` of
    them, in whole row tiles of the grouped product) and runs as many chunks
    as hold the step's held count, so the buffers are a chunk's rows and the
    work follows the count; all the chunks are ``T * k`` rows, so that a
    token whose ``top_k`` are all held loses none, and the result is exact
    at every count.  ``stats``'
    ``held_buffer_rows`` is the rows of the chunks run (a rung of
    :func:`held_rungs`): ``held_assignments`` over it is the buffers' fill,
    and a step that ran every chunk moved every row, as a layer that sizes
    its buffers for the worst case would.  The chunk is in the ``moe``
    event's line and the gauge ``moe.held_chunk_rows``, the ladder's length
    in the gauge ``moe.held_buffer_rungs``.

    ``stats`` (float32 scalars but ``state_updates``): ``load_balance`` = E
    * sum_e f_e P_e with f_e the share of a row's ``seq * k`` assignments
    that expert e got (sums to one over the experts) and P_e the row's mean
    router probability, computed a row of the batch at a time and averaged
    over the rows; ``z_loss`` = mean(logsumexp(router logits)^2), softmax
    scoring only; ``load_max_over_mean`` = the busiest expert's assignments
    over the mean, over the whole batch and all experts; ``dropped`` = the
    held assignments whose sorted row the grouped products do not put
    through its own expert (:func:`_uncovered`): 0 while the sort and the
    group sizes agree; with ``cfg.held``, ``held_assignments`` = the
    assignments that chose a held expert, ``held_buffer_rows`` = the rows
    of the chunks they were moved in, and ``held_output_rms`` = the root
    mean square, over tokens and lanes, of what the held experts add to the
    output (weighted, before the shared expert: 0 where nothing landed).
    With ``cfg.select_bias``: ``bias_absmax`` and
    ``state_updates = {"bias": the bias after this step}``, each entry
    moved by ``bias_update_rate`` up where the expert got fewer assignments
    than the mean, down where more (the loss-free balancing of
    arXiv:2408.15664); the caller hands it to the Runner under the
    variable's full name (``aux["state_updates"]``).
    """
    rows, seq, _ = x.shape
    tokens, num_e, top_k = rows * seq, cfg.num_experts, cfg.top_k
    flat_x = x.reshape(tokens, cfg.d_model)
    _announce(cfg, tokens * top_k)
    with jax.named_scope("router"):
        if cfg.scoring == "sigmoid":
            # Exact float32 products: a selection bias moves by a thousandth
            # a step, which one bf16 pass of the MXU (what an f32 product is
            # by default on the TPU) does not resolve in a logit.
            logits = jnp.dot(flat_x.astype(jnp.float32),
                             params["gate"]["kernel"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            top_vals, top_idx, gates = _route_sigmoid(logits, params, cfg)
        else:
            logits = flat_x.astype(jnp.float32) @ \
                params["gate"]["kernel"].astype(jnp.float32)
            gates = jax.nn.softmax(logits, axis=-1)             # (T, E)
            # The capacity path's whole-batch balance term is not used here.
            top_vals, top_idx, _ = _route(gates, cfg)
            if cfg.route_scale != 1.0:
                top_vals = top_vals * cfg.route_scale
        flat_idx = top_idx.reshape(-1)                          # token-major
        # (rows, E) counts; a row's sum to seq * k.
        counts = jax.vmap(lambda i: jnp.bincount(i, length=num_e))(
            top_idx.reshape(rows, seq * top_k))
        density = counts.astype(jnp.float32) / (seq * top_k)
        mean_gate = gates.reshape(rows, seq, num_e).mean(1)
        group_sizes = counts.sum(0).astype(jnp.int32)
        stats = {"load_balance": jnp.mean(
            num_e * jnp.sum(density * mean_gate, axis=-1))}
        if cfg.scoring == "softmax":
            stats["z_loss"] = jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2)
        stats["load_max_over_mean"] = group_sizes.max().astype(jnp.float32) \
            * num_e / (tokens * top_k)
        if cfg.groups:
            # The groups a token's choices fall in, mean over the tokens.
            stats["groups_reached"] = jnp.mean(jnp.sum(jax.nn.one_hot(
                top_idx // (num_e // cfg.groups), cfg.groups).max(-2), -1))
        if cfg.select_bias:
            load = group_sizes.astype(jnp.float32)
            bias = params["bias"] + cfg.bias_update_rate * jnp.sign(
                load.mean() - load)
            stats["state_updates"] = {"bias": jax.lax.stop_gradient(bias)}
            stats["bias_absmax"] = jnp.max(jnp.abs(bias))
    if cfg.held is not None:
        out = _held_part(params, cfg, flat_x, top_vals, flat_idx,
                         group_sizes, stats)
    else:
        with jax.named_scope("dispatch"):
            # argsort, keeping the sorted keys the same sort produces.
            sorted_experts, order = jax.lax.sort_key_val(
                flat_idx, jnp.arange(tokens * top_k, dtype=flat_idx.dtype))
            inverse = jnp.argsort(order)
            stats["dropped"] = _uncovered(sorted_experts, group_sizes)
            sorted_x = _sorted_rows(flat_x.astype(cfg.dtype), order, inverse,
                                    top_k)
        with jax.named_scope("experts"):
            expert_out = _experts(
                cfg, lambda name: params[name]["kernel"].astype(cfg.dtype),
                sorted_x, group_sizes)                          # (T * k, d)
        with jax.named_scope("dispatch"):
            y = _unsorted_rows(expert_out, order, inverse) \
                .reshape(tokens, top_k, cfg.d_model)
            out = jnp.sum(y.astype(jnp.float32) * top_vals[..., None], axis=1)
    if cfg.shared:
        with jax.named_scope("shared"):
            out = out + _shared_expert(params["shared"], cfg, flat_x,
                                       params.get("shared_gate"))
    return out.reshape(x.shape).astype(x.dtype), stats


def dense_apply(params, cfg, x):
    """Dense all-experts compute (numerics reference; E/k x the FLOPs).

    Every expert's FFN runs on every token and the combine weights zero the
    non-routed pairs — no token is ever dropped, so this is the drop-free
    oracle :func:`apply` is tested against.
    """
    logits = x.astype(jnp.float32) @ params["gate"]["kernel"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                     # (..., E)
    flat_gates = gates.reshape(-1, cfg.num_experts)
    top_vals, top_idx, aux = _route(flat_gates, cfg)
    combine = jnp.zeros_like(flat_gates)
    combine = jax.vmap(lambda c, i, v: c.at[i].set(v),
                       in_axes=(0, 0, 0))(
        combine, top_idx, top_vals).reshape(gates.shape)        # (..., E)

    xc = x.astype(cfg.dtype)
    down = params["down"]["kernel"].astype(cfg.dtype)
    h = _expert_hidden(params, cfg, xc, "...d,edh->...eh")
    per_expert = jnp.einsum("...eh,ehd->...ed", h, down)
    out = jnp.einsum("...ed,...e->...d", per_expert.astype(jnp.float32), combine)
    return out.astype(x.dtype), aux


def reference_apply(params, cfg, x):
    """Per-token loop reference (slow, for numeric tests)."""
    logits = x.astype(jnp.float32) @ params["gate"]["kernel"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    flat_x = x.reshape(-1, cfg.d_model)
    flat_g = gates.reshape(-1, cfg.num_experts)
    outs = []
    for t in range(flat_x.shape[0]):
        vals, idx = jax.lax.top_k(flat_g[t], cfg.top_k)
        vals = vals / vals.sum()
        acc = jnp.zeros((cfg.d_model,), jnp.float32)
        for j in range(cfg.top_k):
            e = idx[j]
            h = jax.nn.gelu(flat_x[t] @ params["up"]["kernel"][e])
            acc = acc + vals[j] * (h @ params["down"]["kernel"][e])
        outs.append(acc)
    return jnp.stack(outs).reshape(x.shape).astype(x.dtype)
