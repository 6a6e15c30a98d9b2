"""Layer 4: static resource audit — per-compile-key cost cards.

Layer 2 (``compile_audit``) proves *which* sweep shapes a Problem/Plan can
ever compile; this layer prices them.  For every compile key the enumerator
predicts, a :class:`CostCard` is derived **without running a solve**: the
engine's sweep cores are traced with ``jax.make_jaxpr`` on
``jax.ShapeDtypeStruct`` inputs rebuilt from the key's components alone
(abstract tracing is O(eqns), independent of ``p`` — pricing a ``p = 10^8``
key takes the same fraction of a second as a toy one).  From the traced
jaxpr:

  * **peak device memory** — argument residents (X / y|Y / GroupSpec
    master arrays) plus a liveness *excess* envelope over the equation
    order: every intermediate, scan carry, and stacked scan output is
    charged while live, with no fusion or donation credit (the engine
    donates nothing), so the envelope can only over-estimate what XLA's
    buffer assignment actually reserves.  The ``resource-audit`` benchmark
    row compiles the same key and asserts
    ``memory_analysis() peak <= CostCard.peak_bytes``.
  * **FLOPs / bytes moved** — loop-expanded (``scan`` by its static
    ``length``, ``while`` by the key's ``max_iter`` bound), cross-checked
    in the benchmark row against XLA's single-count ``cost_analysis()``
    through the unified ``launch.hlo_analysis`` backend.
  * **host<->device transfer per launch** — the sweep arguments the engine
    rebuilds per cohort launch (``X_sub``/``X_subs``, bucketed sub-spec,
    lambda pads, warm starts) versus the session residents; a code change
    that re-ships a full-``p`` operand per segment shows up here
    statically (rule ``resource/transfer-in-segment-regression``).
  * **collective plan** — the fold sweep is re-traced under
    ``shard_map`` on an ``AbstractMesh`` (no multi-device hardware
    needed) and every ``psum``/``all_gather``/... primitive in the body
    is extracted with payload bytes.  Fold sweeps are embarrassingly
    parallel: ANY collective is rule ``resource/unexpected-collective``.
  * **shard layout** — ``launch.mesh.fold_shard_compatible`` semantics and
    the divisibility-degrading rule of ``distributed.sharding.divisible``:
    a configured multi-device mesh whose size does not divide the full
    fold cohort silently degrades every lockstep launch to a single-shard
    vmap (rule ``resource/non-divisible-shard``).

Cards diff against a committed ``analysis/budgets.json`` exactly like
Layer 1-3 findings diff against ``analysis/baseline.json``; rule
``resource/hbm-over-budget`` gates every card's peak against the device
HBM budget.  ``python -m repro.analysis --capacity`` inverts the model:
the peak envelope is affine in ``p`` for a fixed bucket signature, so two
traces fit the line and a confirming trace pins the largest ``p`` that
fits one device — the sizing number for the feature-sharded screening
work.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Iterable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.core import Var

from .findings import Finding
from .compile_audit import (ProblemShape, _pow2_ceil, chunk_lengths,
                            feature_buckets, group_buckets)
from .jaxpr_lint import _sub_jaxprs
from ..launch.hlo_analysis import DEVICE_HBM_BYTES

RULES = (
    "resource/hbm-over-budget",
    "resource/unexpected-collective",
    "resource/non-divisible-shard",
    "resource/transfer-in-segment-regression",
)

#: jaxpr-level collective primitives a sweep body must never contain
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "pbroadcast", "psum_scatter", "reduce_scatter", "pgather",
})

DEFAULT_BUDGETS = {
    # per-device HBM envelope, shared with the roofline/dry-run tooling
    "device_hbm_bytes": DEVICE_HBM_BYTES,
    # collectives allowed inside sweep bodies (none: folds are independent)
    "allowed_collectives": [],
    # per-configuration budgets, keyed by card label:
    #   {"peak_bytes": ..., "transfer_bytes": ...}
    "configs": {},
}


# ---------------------------------------------------------------------------
# jaxpr walkers
# ---------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _is_var(v) -> bool:
    return isinstance(v, Var)


#: primitives whose output is *provably* never a fresh buffer — erased at
#: lowering (``stop_gradient``) or a bitcast of the input (rank-only
#: reshapes).  ``broadcast_in_dim`` and ``transpose`` are deliberately NOT
#: here: XLA materializes a broadcast feeding a batched ``dot_general``
#: (measured on the fold-sweep keys), so aliasing them would break the
#: never-under-estimate contract.  Together with the same-root
#: ``select_n`` rule below this still collapses the in-scan
#: ``lax.cond``-batching artifact — ``select_n(pred, stop_gradient(bX),
#: bX)`` — from three phantom (K, N, p) copies of the design matrix down
#: to the one copy XLA actually allocates.
_VIEW_PRIMS = frozenset({
    "stop_gradient", "reshape", "squeeze", "expand_dims",
})


def _root_map(jaxpr) -> dict:
    """out-var -> root var for pure view chains: ``_VIEW_PRIMS`` outputs
    alias their input, and a ``select_n`` whose value operands all resolve
    to the SAME root is the identity (the cond-batching artifact above)."""
    root: dict = {}

    def r(v):
        return root.get(v, v)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if len(eqn.outvars) != 1:
            continue
        if name in _VIEW_PRIMS and eqn.invars and _is_var(eqn.invars[0]):
            root[eqn.outvars[0]] = r(eqn.invars[0])
        elif name == "select_n" and len(eqn.invars) > 1:
            vals = eqn.invars[1:]
            if all(_is_var(v) for v in vals):
                roots = {r(v) for v in vals}
                if len(roots) == 1:
                    root[eqn.outvars[0]] = roots.pop()
    return root


def excess_bytes(jaxpr) -> int:
    """Peak bytes of values materialized *beyond the jaxpr's own inputs*
    (intermediates, scan carries/stacked outputs, and the jaxpr's outputs),
    over the written equation order.

    View chains (``_root_map``) alias their root and charge nothing; no
    other fusion, aliasing, or donation credit is taken — XLA's buffer
    assignment can only do better, so ``invar bytes + excess_bytes`` is an
    upper envelope of the compiled program's peak allocation (validated
    against ``memory_analysis()`` by the ``resource-audit`` benchmark
    row).  Nested jaxprs (scan/while bodies, cond branches, pjit)
    contribute their own excess beyond their inputs, which alias values
    already charged in the enclosing scope.
    """
    root = _root_map(jaxpr)

    def r(v):
        return root.get(v, v)

    last: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last[r(v)] = i
    for v in jaxpr.outvars:
        if _is_var(v):
            last[r(v)] = len(jaxpr.eqns)

    own = set(jaxpr.invars) | set(jaxpr.constvars)
    live = 0
    held: dict = {}
    peak = 0
    for i, eqn in enumerate(jaxpr.eqns):
        inner = max((excess_bytes(sub) for sub in _sub_jaxprs(eqn)),
                    default=0)
        out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars
                    if r(v) is v)
        peak = max(peak, live + out_b + inner)
        for v in eqn.outvars:
            if r(v) is v and last.get(v, -1) > i:
                held[v] = _aval_bytes(v.aval)
                live += held[v]
        for v in eqn.invars:
            if not _is_var(v):
                continue
            rv = r(v)
            if rv not in own and last.get(rv) == i and rv in held:
                live -= held.pop(rv)
    return peak


def _dot_flops(eqn) -> float:
    out_n = sum(int(np.prod(v.aval.shape, dtype=np.int64))
                for v in eqn.outvars)
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    K = 1
    for d in lhs_c:
        K *= int(lhs_shape[d])
    return 2.0 * out_n * K


def walk_cost(jaxpr, mult: float, while_trips: int,
              flops_moved_colls=None):
    """Loop-expanded (flops, bytes_moved, collectives) over a jaxpr tree.

    ``scan`` scales by its static ``length``; ``while`` by ``while_trips``
    (the key's ``max_iter`` — an upper envelope, where XLA's
    ``cost_analysis`` counts a body once); ``cond`` branches are summed
    (under vmap both branches execute as ``select``).  Collectives are
    reported as ``prim -> {"count", "payload_bytes"}``.
    """
    acc = flops_moved_colls if flops_moved_colls is not None else \
        {"flops": 0.0, "bytes_moved": 0.0, "collectives": {}}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            inner_mult = mult * max(int(eqn.params.get("length", 1)), 1)
            for sub in _sub_jaxprs(eqn):
                walk_cost(sub, inner_mult, while_trips, acc)
            continue
        if name == "while":
            inner_mult = mult * max(while_trips, 1)
            for sub in _sub_jaxprs(eqn):
                walk_cost(sub, inner_mult, while_trips, acc)
            continue
        subs = list(_sub_jaxprs(eqn))
        if subs:
            for sub in subs:
                walk_cost(sub, mult, while_trips, acc)
            continue
        if name == "dot_general":
            acc["flops"] += mult * _dot_flops(eqn)
        io_bytes = (sum(_aval_bytes(v.aval) for v in eqn.invars
                        if _is_var(v))
                    + sum(_aval_bytes(v.aval) for v in eqn.outvars))
        acc["bytes_moved"] += mult * io_bytes
        if name in COLLECTIVE_PRIMS:
            ent = acc["collectives"].setdefault(
                name, {"count": 0, "payload_bytes": 0})
            ent["count"] += int(mult)
            ent["payload_bytes"] += int(
                mult * sum(_aval_bytes(v.aval) for v in eqn.invars
                           if _is_var(v)))
    return acc


# ---------------------------------------------------------------------------
# Compile key -> abstract sweep arguments
# ---------------------------------------------------------------------------

def _abstract_spec(G: int, p: int, n_max: int, dtype, lead=()):
    """A GroupSpec pytree whose array leaves are ShapeDtypeStructs — enough
    to trace any sweep core at arbitrary dimensions with zero bytes
    materialized.  ``lead`` prepends a fold axis for stacked sub-specs."""
    from ..core.groups import GroupSpec
    S = jax.ShapeDtypeStruct
    n_max = max(int(n_max), 1)
    leaves = (S(lead + (G,), jnp.int32), S(lead + (G,), jnp.int32),
              S(lead + (p,), jnp.int32), S(lead + (G,), dtype),
              S(lead + (G, n_max), jnp.int32),
              S(lead + (G, n_max), jnp.bool_),
              None)                       # feature_weights: unweighted
    return GroupSpec.tree_unflatten((G, p, n_max, False), leaves)


def _strip_loss(key: tuple):
    """Split a compile key into (dims, loss-name).  Since the loss became
    a key dimension it rides at the END of every tuple; keys from before
    that change (committed baselines, hand-written tests) have no suffix
    and price as squared."""
    if key and isinstance(key[-1], str) and key[-1] in ("squared",
                                                        "logistic"):
        return key[:-1], key[-1]
    return key, "squared"


def _args_for_key(key: tuple):
    """(traceable fn, abstract args, per-arg session-resident flags).

    Mirrors the engine's sweep launch argument construction exactly
    (``path_engine`` single-path launches, ``cv._fold_sweep`` cohort
    launches); the resident flags mark operands that live on the device
    for the whole session (X, y/Y, the parent GroupSpec, fold means) —
    everything else is rebuilt and shipped per launch.
    """
    from ..core.losses import get_loss
    from ..core.path_engine import sweep_nn_core, sweep_sgl_core
    key, loss_name = _strip_loss(key)
    loss = get_loss(loss_name)
    kind = key[0]
    S = jax.ShapeDtypeStruct
    if kind == "sgl":
        (_, N, p, G, dtype_s, max_iter, check_every, pallas,
         p_b, g_b, max_size, len2) = key
        dt = jnp.dtype(dtype_s)
        fn = functools.partial(sweep_sgl_core, max_iter=max_iter,
                               check_every=check_every, use_pallas=pallas,
                               loss=loss)
        args = [S((N, p), dt), S((N, p_b), dt), S((N,), dt),
                _abstract_spec(G, p, max_size, dt),
                _abstract_spec(g_b, p_b, max_size, dt),
                0.5, S((), dt), S((len2,), dt), S((len2,), jnp.bool_),
                S((p_b,), dt), 1e-9, 1.0]
        resident = [True, False, True, True, False, False, False, False,
                    False, False, False, False]
        return fn, args, resident
    if kind == "nn":
        _, N, p, dtype_s, max_iter, check_every, pallas, p_b, len2 = key
        dt = jnp.dtype(dtype_s)
        fn = functools.partial(sweep_nn_core, max_iter=max_iter,
                               check_every=check_every, use_pallas=pallas)
        args = [S((N, p), dt), S((N, p_b), dt), S((N,), dt), S((), dt),
                S((len2,), dt), S((len2,), jnp.bool_), S((p_b,), dt),
                1e-9, 1.0]
        resident = [True, False, True, False, False, False, False, False,
                    False]
        return fn, args, resident
    if kind == "sgl-folds":
        (_, Ka, N, p, G, dtype_s, max_iter, check_every, _mesh,
         p_b, g_b, max_size, len2, centered, pallas) = key
        from ..core.cv import _SGL_SWEEP_AXES
        dt = jnp.dtype(dtype_s)
        axes = _SGL_SWEEP_AXES + ((0,) if centered else ())
        core = functools.partial(sweep_sgl_core, max_iter=max_iter,
                                 check_every=check_every, use_pallas=pallas,
                                 loss=loss)
        fn = jax.vmap(core, in_axes=axes)
        args = [S((N, p), dt), S((Ka, N, p_b), dt), S((Ka, N), dt),
                _abstract_spec(G, p, max_size, dt),
                _abstract_spec(g_b, p_b, max_size, dt, lead=(Ka,)),
                0.5, S((Ka,), dt), S((Ka, len2), dt),
                S((Ka, len2), jnp.bool_), S((Ka, p_b), dt), 1e-9,
                S((Ka,), dt)]
        resident = [True, False, True, True, False, False, False, False,
                    False, False, False, False]
        if centered:
            args.append(S((Ka, p), dt))
            resident.append(True)
        return fn, args, resident
    if kind == "nn-folds":
        (_, Ka, N, p, dtype_s, max_iter, check_every, _mesh, p_b, len2,
         pallas) = key
        from ..core.cv import _NN_SWEEP_AXES
        dt = jnp.dtype(dtype_s)
        core = functools.partial(sweep_nn_core, max_iter=max_iter,
                                 check_every=check_every, use_pallas=pallas)
        fn = jax.vmap(core, in_axes=_NN_SWEEP_AXES)
        args = [S((N, p), dt), S((Ka, N, p_b), dt), S((Ka, N), dt),
                S((Ka,), dt), S((Ka, len2), dt), S((Ka, len2), jnp.bool_),
                S((Ka, p_b), dt), 1e-9, S((Ka,), dt)]
        resident = [True, False, True, False, False, False, False, False,
                    False]
        return fn, args, resident
    if kind in ("sgl-feat", "nn-feat"):
        # PER-DEVICE card: one shard block priced at the static width
        # envelope (shard_width_bound) — exactly the program each mesh
        # device runs under shard_map, so the HBM gate applies per device
        # and --capacity shows the ~linear max-p scaling sharding buys
        from ..distributed.feature_shard import feature_ops
        return _feat_trace(key, feature_ops(1, None), 1)
    raise ValueError(f"unknown compile-key kind {kind!r}")


def _feat_trace(key: tuple, ops, S_lead: int):
    """(fn, abstract args, resident flags) of a feature-sharded sweep key,
    with ``S_lead`` stacked shard blocks executed by ``ops``.

    The block width is the static envelope
    ``shard_width_bound(p, n_units, S_effective, max_size)`` — the count is
    degraded through ``effective_shards`` first (the partitioner's rule),
    so a non-dividing request is priced at the WIDER blocks it actually
    produces, never the optimistic ``p / requested``."""
    from ..core.path_engine import sweep_nn_core_feat, sweep_sgl_core_feat
    from ..distributed.feature_shard import (effective_shards,
                                             shard_width_bound)
    S = jax.ShapeDtypeStruct
    kind = key[0]
    if kind == "sgl-feat":
        (_, Sn, N, p, G, dtype_s, max_iter, check_every, _mesh_flag,
         p_b, g_b, max_size, len2) = key
        dt = jnp.dtype(dtype_s)
        S_eff = effective_shards(G, Sn)
        p_sh = shard_width_bound(p, G, S_eff, max_size)
        G_sh = max(G // S_eff, 1)
        fn = functools.partial(sweep_sgl_core_feat, ops=ops,
                               max_iter=max_iter, check_every=check_every)
        args = [S((S_lead, N, p_sh), dt), S((N, p_b), dt), S((N,), dt),
                _abstract_spec(G_sh, p_sh, max_size, dt, lead=(S_lead,)),
                _abstract_spec(g_b, p_b, max_size, dt),
                0.5, S((), dt), S((len2,), dt), S((len2,), jnp.bool_),
                S((p_b,), dt), 1e-9, 1.0]
        resident = [True, False, True, True, False, False, False, False,
                    False, False, False, False]
        return fn, args, resident
    (_, Sn, N, p, dtype_s, max_iter, check_every, _mesh_flag, p_b,
     len2) = key
    dt = jnp.dtype(dtype_s)
    S_eff = effective_shards(p, Sn)
    p_sh = shard_width_bound(p, p, S_eff, 1)
    fn = functools.partial(sweep_nn_core_feat, ops=ops, max_iter=max_iter,
                           check_every=check_every)
    args = [S((S_lead, N, p_sh), dt), S((N, p_b), dt), S((N,), dt),
            S((), dt), S((len2,), dt), S((len2,), jnp.bool_), S((p_b,), dt),
            1e-9, 1.0]
    resident = [True, False, True, False, False, False, False, False,
                False]
    return fn, args, resident


#: index of ``max_iter`` in each compile-key tuple (the while-trip bound
#: ``walk_cost`` expands iteration loops by)
_MAX_ITER_IDX = {"sgl": 5, "nn": 4, "sgl-folds": 6, "nn-folds": 5,
                 "sgl-feat": 6, "nn-feat": 5}


def _max_iter_of(key: tuple) -> int:
    return int(key[_MAX_ITER_IDX[key[0]]])


def _tree_bytes(x) -> int:
    return sum(_aval_bytes(l) for l in jax.tree_util.tree_leaves(x)
               if hasattr(l, "shape"))


# ---------------------------------------------------------------------------
# Cost cards
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CostCard:
    """Static resource prediction for one compile key."""
    label: str
    key: tuple
    arg_bytes: int               # all sweep operands (avals)
    out_bytes: int               # sweep outputs (betas/thetas/cthetas/...)
    excess_bytes: int            # liveness envelope beyond the operands
    peak_bytes: int              # arg_bytes + excess_bytes (>= XLA peak)
    resident_bytes: int          # session-persistent operands (X, Y, spec)
    transfer_h2d_bytes: int      # per-launch host->device (arg - resident)
    transfer_d2h_bytes: int      # per-launch harvest envelope (= out)
    flops: float                 # loop-expanded envelope
    bytes_moved: float           # loop-expanded eqn traffic
    collectives: dict            # prim -> {count, payload_bytes}
    shard: dict                  # mesh/cohort divisibility summary

    @property
    def transfer_bytes(self) -> int:
        return self.transfer_h2d_bytes + self.transfer_d2h_bytes

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = [repr(k) if not isinstance(
            k, (int, float, str, bool, type(None))) else k for k in self.key]
        d["transfer_bytes"] = self.transfer_bytes
        return d


def card_for_key(key: tuple, label: str = "", *, mesh_size: int = 1,
                 n_folds: Optional[int] = None) -> CostCard:
    """Derive the :class:`CostCard` of one compile key by abstract tracing.

    ``mesh_size``/``n_folds`` describe the configured fold mesh for the
    shard-layout summary (1 = unsharded); they do not affect the trace —
    collective plans are extracted separately by
    :func:`fold_collective_plan`."""
    fn, args, resident = _args_for_key(key)
    closed = jax.make_jaxpr(fn)(*args)
    arg_bytes = (sum(_aval_bytes(v.aval) for v in closed.jaxpr.invars)
                 + sum(_aval_bytes(v.aval) for v in closed.jaxpr.constvars))
    out_bytes = sum(_aval_bytes(v.aval) for v in closed.jaxpr.outvars)
    excess = excess_bytes(closed.jaxpr)
    res_bytes = sum(_tree_bytes(a) for a, r in zip(args, resident) if r)
    h2d = sum(_tree_bytes(a) for a, r in zip(args, resident) if not r)
    cost = walk_cost(closed.jaxpr, 1.0, _max_iter_of(key))
    if key[0].endswith("-feat"):
        # feature mesh: 'rows' are the shard blocks; divisibility is the
        # partitioner's group-count rule (effective == requested)
        from ..distributed.feature_shard import effective_shards
        Sn = int(key[1])
        n_units = int(key[4]) if key[0] == "sgl-feat" else int(key[3])
        S_eff = effective_shards(n_units, Sn)
        shard = {
            "mesh_size": S_eff,
            "rows": Sn,
            "full_cohort": Sn,
            "sharded": bool(S_eff > 1),
            "divisible": bool(S_eff == Sn),
        }
        cost = dict(cost, collectives=feature_collective_plan(key))
    else:
        Ka = key[1] if key[0].endswith("-folds") else 1
        n_folds = Ka if n_folds is None else n_folds
        shard = {
            "mesh_size": int(mesh_size),
            "rows": int(Ka),
            "full_cohort": int(n_folds),
            "sharded": bool(mesh_size > 1 and Ka % mesh_size == 0),
            "divisible": bool(mesh_size <= 1 or n_folds % mesh_size == 0),
        }
    return CostCard(
        label=label or key[0], key=key, arg_bytes=arg_bytes,
        out_bytes=out_bytes, excess_bytes=excess,
        peak_bytes=arg_bytes + excess, resident_bytes=res_bytes,
        transfer_h2d_bytes=h2d, transfer_d2h_bytes=out_bytes,
        flops=cost["flops"], bytes_moved=cost["bytes_moved"],
        collectives=cost["collectives"], shard=shard)


def compile_key(key: tuple):
    """AOT-compile the sweep a key names (from ShapeDtypeStructs — no data).
    Used by the ``resource-audit`` benchmark row to check the static card
    against XLA's own ``memory_analysis``/``cost_analysis``."""
    fn, args, _ = _args_for_key(key)
    return jax.jit(fn).lower(*args).compile()


# ---------------------------------------------------------------------------
# Collective plan (shard_map over an AbstractMesh — no devices needed)
# ---------------------------------------------------------------------------

def fold_collective_plan(key: tuple, mesh_size: int = 2) -> dict:
    """Trace the fold sweep a ``*-folds`` key names under ``shard_map`` on
    an abstract 'fold' mesh of ``mesh_size`` shards and extract every
    collective primitive in the body with loop-expanded payload bytes.

    Fold sweeps are embarrassingly parallel — the expected plan is empty;
    anything else means a cross-fold reduction leaked into the sweep body
    and every launch now serializes on the interconnect."""
    if not key[0].endswith("-folds"):
        raise ValueError("collective plans are defined for fold keys")
    from ..launch.mesh import abstract_fold_mesh, shard_over_folds
    fn, args, _ = _args_for_key(key)
    Ka = int(key[1])
    if Ka % mesh_size != 0:
        raise ValueError(f"cohort {Ka} does not divide mesh {mesh_size}")
    if key[0] == "sgl-folds":
        from ..core.cv import _SGL_SWEEP_AXES
        axes = _SGL_SWEEP_AXES + ((0,) if key[13] else ())
    else:
        from ..core.cv import _NN_SWEEP_AXES
        axes = _NN_SWEEP_AXES
    mesh = abstract_fold_mesh(mesh_size)
    sharded = shard_over_folds(fn, mesh, axes)
    closed = jax.make_jaxpr(sharded)(*args)
    cost = walk_cost(closed.jaxpr, 1.0, _max_iter_of(key))
    return cost["collectives"]


def feature_collective_plan(key: tuple, screen_fn=None) -> dict:
    """Collective plan of the feature-sharded layer for a ``*-feat`` key:
    the canonical screen + certification + partial-fit composite is traced
    under ``shard_map`` on an abstract 'feature' mesh (no multi-device
    hardware needed) and every collective primitive is extracted with
    payload bytes.

    The sharded layer is built so the ONLY collective is the psum of
    N-sized partial fits (``FeatureOps.fsum``); screens and group stats
    are feature-local ``fmap`` programs, and the global dual-scaling
    reduction runs on the gathered (S, G_shard) stack OUTSIDE the mapped
    body.  In particular no ``all_gather`` of shard blocks may appear — a
    full-X gather would erase the memory win sharding exists for.  Budget
    entries for these cards therefore carry
    ``"allowed_collectives": ["psum"]``; anything else fires
    ``resource/unexpected-collective``.

    ``screen_fn(ops, Xs, specs_or_None, *std_args)`` substitutes the
    screen stage (the seeded-violation tests inject an illegally
    gathering screen); None uses the engine's own grid screen."""
    if not key[0].endswith("-feat"):
        raise ValueError("feature collective plans are defined for "
                         "*-feat keys")
    key, _loss_name = _strip_loss(key)   # sharded layer is squared-only
    from ..distributed.feature_shard import (cert_nn, cert_sgl,
                                             effective_shards, feature_ops,
                                             shard_width_bound,
                                             sharded_fit)
    from ..launch.mesh import abstract_feature_mesh
    S = jax.ShapeDtypeStruct
    kind = key[0]
    if kind == "sgl-feat":
        (_, Sn, N, p, G, dtype_s, max_iter, _ce, _m, _p_b, _g_b,
         max_size, len2) = key
        n_units = G
    else:
        _, Sn, N, p, dtype_s, max_iter, _ce, _m, _p_b, len2 = key
        n_units, max_size = p, 1
    S_eff = effective_shards(n_units, int(Sn))
    if S_eff <= 1:
        return {}
    dt = jnp.dtype(dtype_s)
    ops = feature_ops(S_eff, abstract_feature_mesh(S_eff))
    p_sh = shard_width_bound(p, n_units, S_eff, max_size)
    G_sh = max(n_units // S_eff, 1)
    Xs_a = S((S_eff, N, p_sh), dt)
    vecs = [S((N,), dt) for _ in range(3)]          # y, theta_bar, n_vec
    lams = S((len2,), dt)
    col_s = S((S_eff, p_sh), dt)
    if kind == "sgl-feat":
        from ..core.screening import tlfre_screen_grid_feat
        specs_a = _abstract_spec(G_sh, p_sh, max_size, dt, lead=(S_eff,))
        gspec_a = S((S_eff, G_sh), dt)

        def prog(Xs, specs, y, lams, theta, nvec, coln, gspec, beta_s,
                 rho):
            screen = screen_fn or tlfre_screen_grid_feat
            kept = screen(ops, Xs, specs, y, 0.5, lams, theta, nvec,
                          coln, gspec)
            fit = sharded_fit(ops, Xs, beta_s)       # THE one psum site
            c_s, s = cert_sgl(ops, Xs, specs, rho / 2.0, 0.5)
            return kept, fit, c_s, s

        args = [Xs_a, specs_a, vecs[0], lams, vecs[1], vecs[2], col_s,
                gspec_a, col_s, vecs[0]]
    else:
        from ..core.dpc import dpc_screen_grid_feat

        def prog(Xs, y, lams, theta, nvec, coln, beta_s, rho):
            screen = screen_fn or dpc_screen_grid_feat
            kept = screen(ops, Xs, y, lams, theta, nvec, coln)
            fit = sharded_fit(ops, Xs, beta_s)
            c_s, s = cert_nn(ops, Xs, rho / 2.0)
            return kept, fit, c_s, s

        args = [Xs_a, vecs[0], lams, vecs[1], vecs[2], col_s, col_s,
                vecs[0]]
    closed = jax.make_jaxpr(prog)(*args)
    return walk_cost(closed.jaxpr, 1.0, _max_iter_of(key))["collectives"]


# ---------------------------------------------------------------------------
# Budgets + findings
# ---------------------------------------------------------------------------

def load_budgets(path: Optional[str]) -> dict:
    budgets = {k: (dict(v) if isinstance(v, dict) else v)
               for k, v in DEFAULT_BUDGETS.items()}
    if path:
        with open(path) as f:
            data = json.load(f)
        for k in ("device_hbm_bytes", "allowed_collectives", "configs"):
            if k in data:
                budgets[k] = data[k]
    return budgets


def write_budgets(cards: Iterable[CostCard], path: str, *,
                  hbm_bytes: Optional[int] = None,
                  slack: float = 1.25) -> None:
    """Record the current cards as budgets (peak/transfer x ``slack``
    headroom, deterministically sorted) — the resource-layer analogue of
    ``--write-baseline``."""
    configs = {}
    for c in sorted(cards, key=lambda c: c.label):
        entry = {
            "peak_bytes": int(c.peak_bytes * slack),
            "transfer_bytes": int(c.transfer_bytes * slack),
        }
        if c.key[0].endswith("-feat"):
            entry["allowed_collectives"] = ["psum"]
        configs[c.label] = entry
    out = {
        "device_hbm_bytes": int(hbm_bytes
                                or DEFAULT_BUDGETS["device_hbm_bytes"]),
        "allowed_collectives": [],
        "configs": configs,
    }
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")


def check_cards(cards: Iterable[CostCard], budgets: dict) -> list:
    """Diff cost cards against the budget file; one finding per violated
    resource rule."""
    findings = []
    hbm = int(budgets.get("device_hbm_bytes",
                          DEFAULT_BUDGETS["device_hbm_bytes"]))
    allowed = set(budgets.get("allowed_collectives", ()))
    configs = budgets.get("configs", {})
    for c in cards:
        if c.peak_bytes > hbm:
            findings.append(Finding(
                "resource/hbm-over-budget", "error", c.label,
                f"static peak {c.peak_bytes / 1e9:.2f} GB exceeds the "
                f"{hbm / 1e9:.1f} GB device budget for key {c.key[0]} "
                f"(args {c.arg_bytes / 1e9:.2f} GB + excess "
                f"{c.excess_bytes / 1e9:.2f} GB)"))
        # a config entry may widen the global allow-list for ITS card only
        # (feature-sharded sweeps legitimately psum partial fits; fold
        # sweeps stay embarrassingly parallel)
        entry_allowed = configs.get(c.label, {}).get("allowed_collectives")
        allowed_here = (allowed | set(entry_allowed)
                        if entry_allowed is not None else allowed)
        for prim, ent in sorted(c.collectives.items()):
            if prim not in allowed_here:
                findings.append(Finding(
                    "resource/unexpected-collective", "error",
                    f"{c.label}:{prim}",
                    f"sweep body fires {prim} x{ent['count']} moving "
                    f"{ent['payload_bytes'] / 1e6:.2f} MB — only "
                    f"{sorted(allowed_here) or 'no collectives'} are "
                    f"allowed for this card"))
        if not c.shard["divisible"]:
            findings.append(Finding(
                "resource/non-divisible-shard", "error", c.label,
                f"configured fold mesh of {c.shard['mesh_size']} devices "
                f"does not divide the {c.shard['full_cohort']}-fold "
                f"cohort — every lockstep launch silently degrades to a "
                f"single-shard vmap (fold_shard_compatible rejects it)"))
        entry = configs.get(c.label)
        if entry and c.transfer_bytes > int(entry.get(
                "transfer_bytes", c.transfer_bytes)):
            findings.append(Finding(
                "resource/transfer-in-segment-regression", "error", c.label,
                f"per-launch transfer grew to "
                f"{c.transfer_bytes / 1e6:.2f} MB "
                f"(h2d {c.transfer_h2d_bytes / 1e6:.2f} + d2h "
                f"{c.transfer_d2h_bytes / 1e6:.2f}), above the budgeted "
                f"{int(entry['transfer_bytes']) / 1e6:.2f} MB — a "
                f"full-p operand is being re-shipped per segment"))
    return findings


def verify_shard_layout(mesh_size: int, n_folds: int,
                        label: str = "layout") -> list:
    """Stand-alone shard-layout verifier: the divisibility-degrading rule
    (``distributed.sharding.divisible``) applied to a fold cohort."""
    from ..distributed.sharding import divisible
    findings = []
    if mesh_size > 1 and not divisible(n_folds, {"fold": mesh_size},
                                       "fold"):
        findings.append(Finding(
            "resource/non-divisible-shard", "error", label,
            f"fold mesh of {mesh_size} devices does not divide "
            f"n_folds={n_folds}; shard_over_folds falls back to a "
            f"single-shard vmap and the extra devices idle"))
    return findings


# ---------------------------------------------------------------------------
# Representative audit (the Layer-4 ``run`` entry)
# ---------------------------------------------------------------------------

def dominating_key(shape: ProblemShape, plan, kind: str,
                   n_folds: Optional[int] = None) -> tuple:
    """The peak-memory-dominating member of the key universe for one
    (shape, plan, verb): every byte term is monotone in (p_b, g_b, len2,
    Ka), so the maximal ladder values price the whole universe."""
    from .compile_audit import _grid_len, _resolve_pallas
    N, p, G = shape.N, shape.p, shape.G
    J = _grid_len(plan)
    pallas = _resolve_pallas(plan, shape.dtype)
    loss = plan.resolved_loss(shape.loss)
    if loss != "squared" or shape.weighted or \
            getattr(plan, "feature_weights", None) is not None:
        pallas = False         # fused kernels are squared/unweighted-only
    p_b = max(feature_buckets(p, plan.min_bucket))
    if n_folds is None:
        n_folds = (len(plan.folds) if plan.folds is not None
                   else plan.n_folds)
    if kind == "path":
        len2 = max(chunk_lengths(J, plan.chunk_init, 64))
        shards = int(getattr(plan, "feature_shards", 0))
        if shape.penalty == "sgl":
            g_b = max(max(group_buckets(G, plan.min_group_bucket)), G)
            if shards > 1:
                from ..distributed.feature_shard import effective_shards
                S_eff = effective_shards(G, shards)
                if S_eff > 1:
                    # runtime keys carry the EFFECTIVE shard count; the
                    # mesh flag does not affect pricing (False here)
                    return ("sgl-feat", S_eff, N, p, G, shape.dtype,
                            plan.max_iter, plan.check_every, False, p_b,
                            g_b, shape.max_size, len2, loss)
            return ("sgl", N, p, G, shape.dtype, plan.max_iter,
                    plan.check_every, pallas, p_b, g_b, shape.max_size,
                    len2, loss)
        if shards > 1:
            from ..distributed.feature_shard import effective_shards
            S_eff = effective_shards(p, shards)
            if S_eff > 1:
                return ("nn-feat", S_eff, N, p, shape.dtype, plan.max_iter,
                        plan.check_every, False, p_b, len2, "squared")
        return ("nn", N, p, shape.dtype, plan.max_iter, plan.check_every,
                pallas, p_b, len2, "squared")
    len2 = max(chunk_lengths(J, plan.chunk_init, plan.chunk_cap))
    if shape.penalty == "sgl":
        g_b = max(group_buckets(G, plan.min_group_bucket))
        return ("sgl-folds", n_folds, N, p, G, shape.dtype, plan.max_iter,
                plan.check_every, plan.mesh, p_b, g_b, shape.max_size,
                len2, plan.center == "per-fold", pallas, loss)
    return ("nn-folds", n_folds, N, p, shape.dtype, plan.max_iter,
            plan.check_every, plan.mesh, p_b, len2, pallas, "squared")


def audit_cards(shapes=None, plan=None, n_folds: int = 4,
                mesh_size: int = 1) -> list:
    """Cost cards for the representative configurations (the same shapes
    Layer 2 audits), one per (penalty, dtype, verb) — each priced at its
    dominating key."""
    from ..core.problem import Plan
    plan = plan or Plan(n_lambdas=40, n_folds=n_folds)
    shapes = shapes or [
        ProblemShape(N=100, p=500, G=50, max_size=10, penalty="sgl",
                     dtype="float64"),
        ProblemShape(N=100, p=500, G=50, max_size=10, penalty="sgl",
                     dtype="float32"),
        ProblemShape(N=80, p=300, G=0, max_size=0, penalty="nn_lasso",
                     dtype="float64"),
    ]
    cards = []
    for shape in shapes:
        for kind in ("path", "cv"):
            key = dominating_key(shape, plan, kind, n_folds=n_folds)
            label = f"{shape.penalty}[{shape.dtype}]/{kind}"
            cards.append(card_for_key(key, label, mesh_size=mesh_size,
                                      n_folds=n_folds))
    return cards


def feature_audit_cards(shapes=None, plan=None,
                        feature_shards: int = 8) -> list:
    """Per-device cost cards for the feature-sharded path sweeps: the
    same representative shapes, priced at the shard-width envelope with
    the collective plan traced on an abstract 'feature' mesh."""
    from ..core.problem import Plan
    plan = plan or Plan(n_lambdas=40, n_folds=4)
    plan = plan.with_(feature_shards=feature_shards)
    shapes = shapes or [
        ProblemShape(N=100, p=500, G=50, max_size=10, penalty="sgl",
                     dtype="float64"),
        ProblemShape(N=100, p=500, G=50, max_size=10, penalty="sgl",
                     dtype="float32"),
        ProblemShape(N=80, p=300, G=0, max_size=0, penalty="nn_lasso",
                     dtype="float64"),
    ]
    cards = []
    for shape in shapes:
        key = dominating_key(shape, plan, "path")
        if not key[0].endswith("-feat"):
            continue                  # degenerate: nothing > 1 divides
        label = (f"{shape.penalty}[{shape.dtype}]"
                 f"/path-feat{feature_shards}")
        cards.append(card_for_key(key, label))
    return cards


def run(budgets: Optional[str] = None) -> list:
    """CLI layer entry: price the representative configurations (plus
    their feature-sharded path variants), extract the sharded fold sweeps'
    collective plans on an abstract 2-device mesh, and diff everything
    against ``analysis/budgets.json``."""
    from ..core.problem import Plan
    budget_data = load_budgets(budgets)
    plan = Plan(n_lambdas=40, n_folds=4)
    cards = audit_cards(plan=plan, n_folds=4, mesh_size=1)
    cards.extend(feature_audit_cards(plan=plan, feature_shards=8))
    # the loss dimension gets its own card: the logistic path sweep traces
    # a different gap certificate (folds are squared-only, so path kind)
    logit = ProblemShape(N=100, p=500, G=50, max_size=10, penalty="sgl",
                         dtype="float64", loss="logistic")
    cards.append(card_for_key(dominating_key(logit, plan, "path"),
                              "sgl[logistic]/path"))
    # re-price the fold cards' collective plans under a sharded layout:
    # AbstractMesh tracing needs no multi-device hardware
    priced = []
    for c in cards:
        if c.key[0].endswith("-folds"):
            colls = fold_collective_plan(c.key, mesh_size=2)
            shard = dict(c.shard, mesh_size=2,
                         sharded=c.shard["rows"] % 2 == 0,
                         divisible=c.shard["full_cohort"] % 2 == 0)
            c = dataclasses.replace(c, collectives=colls, shard=shard)
        priced.append(c)
    findings = check_cards(priced, budget_data)
    # layout sanity of the mesh constructor contract itself
    findings.extend(verify_shard_layout(1, plan.n_folds, "default-plan"))
    return findings


# ---------------------------------------------------------------------------
# Capacity planner (--capacity): invert the model for max p per device
# ---------------------------------------------------------------------------

def _capacity_key(penalty: str, dtype: str, mode: str, p: int, *, N: int,
                  group_size: int, plan, survivors: Optional[int],
                  feature_shards: int = 0) -> tuple:
    """The dominating key of a scaled-up problem: ``G = p / group_size``
    groups of ``group_size``.  ``survivors`` caps the solve bucket (the
    screening win: only ~survivors features reach FISTA); ``None`` prices
    the unscreened worst case (``p_b = p``).  ``feature_shards > 1``
    (path mode only — fold SWEEPS keep the full design) prices the
    feature-sharded key: the per-device card then holds one shard-width
    block of X instead of all ``p`` columns."""
    J = (len(plan.lambdas) if plan.lambdas is not None
         else int(plan.n_lambdas))
    if survivors is None:
        p_b = p
    else:
        p_b = min(_pow2_ceil(max(int(survivors), 1)), p)
    cap = 64 if mode == "path" else plan.chunk_cap
    len2 = max(chunk_lengths(J, plan.chunk_init, cap))
    n_folds = (len(plan.folds) if plan.folds is not None
               else plan.n_folds)
    shards = int(feature_shards) if mode == "path" else 0
    if penalty == "sgl":
        G = max(p // group_size, 1)
        g_b = min(_pow2_ceil(max(p_b // group_size, 1) + 1), G + 1)
        if mode == "path":
            if shards > 1:
                from ..distributed.feature_shard import effective_shards
                S_eff = effective_shards(G, shards)
                if S_eff > 1:
                    return ("sgl-feat", S_eff, N, p, G, dtype,
                            plan.max_iter, plan.check_every, False, p_b,
                            g_b, group_size, len2)
            return ("sgl", N, p, G, dtype, plan.max_iter,
                    plan.check_every, False, p_b, g_b, group_size, len2)
        return ("sgl-folds", n_folds, N, p, G, dtype, plan.max_iter,
                plan.check_every, None, p_b, g_b, group_size, len2,
                plan.center == "per-fold", False)
    if mode == "path":
        if shards > 1:
            from ..distributed.feature_shard import effective_shards
            S_eff = effective_shards(p, shards)
            if S_eff > 1:
                return ("nn-feat", S_eff, N, p, dtype, plan.max_iter,
                        plan.check_every, False, p_b, len2)
        return ("nn", N, p, dtype, plan.max_iter, plan.check_every, False,
                p_b, len2)
    return ("nn-folds", n_folds, N, p, dtype, plan.max_iter,
            plan.check_every, None, p_b, len2, False)


def _peak_at(p: int, penalty, dtype, mode, *, N, group_size, plan,
             survivors, feature_shards: int = 0) -> int:
    key = _capacity_key(penalty, dtype, mode, p, N=N,
                        group_size=group_size, plan=plan,
                        survivors=survivors, feature_shards=feature_shards)
    return card_for_key(key).peak_bytes


def capacity_max_p(penalty: str, dtype: str, mode: str, *, plan,
                   hbm_bytes: int, N: int = 1000, group_size: int = 10,
                   survivors: Optional[int] = 16384,
                   feature_shards: int = 0) -> int:
    """Largest ``p`` whose dominating sweep key fits ``hbm_bytes``.

    For a fixed bucket signature the peak envelope is affine in ``p``
    (X, group ids, the full-p correlation outputs and the in-scan GEMV
    temporary all scale linearly; everything else is pinned by the
    bucket), so two traces fit the line, one confirming trace validates
    the answer, and a short geometric backoff corrects ladder-boundary
    effects.

    With ``feature_shards > 1`` every probed ``p`` is aligned so the
    group (feature) count divides the shard count — the regime the
    partitioner actually runs at full width; unaligned ``p`` would
    silently degrade to fewer shards and price wider blocks.  The
    per-device block width is then ``~p / S``, so the answer scales
    ~linearly in the shard count."""
    shards = int(feature_shards) if mode == "path" else 0
    q = 1
    if shards > 1:
        q = group_size * shards if penalty == "sgl" else shards

    def _align(v: int) -> int:
        return max(q * (v // q), q) if q > 1 else v

    kw = dict(N=N, group_size=group_size, plan=plan, survivors=survivors,
              feature_shards=shards)
    p1, p2 = 1 << 17, 1 << 19
    if survivors is not None:
        p1 = max(p1, _pow2_ceil(int(survivors)) * 2)
        p2 = max(p2, p1 * 4)
    p1, p2 = _align(p1), _align(p2)
    f1 = _peak_at(p1, penalty, dtype, mode, **kw)
    # first probe already over budget: walk the probe pair down until the
    # lower probe fits (the line is re-fit in the fitting regime), giving
    # up only when even a trivial problem is over budget
    while f1 > hbm_bytes and p1 > (1 << 12):
        p1, p2 = max(_align(p1 // 4), _align(1 << 12)), p1
        f1 = _peak_at(p1, penalty, dtype, mode, **kw)
    if f1 > hbm_bytes:
        return 0
    f2 = _peak_at(p2, penalty, dtype, mode, **kw)
    slope = (f2 - f1) / float(p2 - p1)
    if slope <= 0:
        raise RuntimeError("peak model is not increasing in p")
    base = f1 - slope * p1
    cand = _align(max(int((hbm_bytes - base) / slope), p1))
    for _ in range(20):
        if _peak_at(cand, penalty, dtype, mode, **kw) <= hbm_bytes:
            return cand
        cand = _align(int(cand * 0.96))
    return cand


def capacity_table(plan=None, *, hbm_bytes: Optional[int] = None,
                   N: int = 1000, group_size: int = 10,
                   survivors: int = 16384,
                   feature_shards: int = 8) -> list:
    """``--capacity`` rows: max p per device for every (penalty, dtype,
    verb), screened (solve bucket capped at ``survivors`` features — the
    TLFre operating regime) and unscreened (``p_b = p`` worst case).

    ``max_p_sharded`` prices the same screened regime under
    ``feature_shards``-way column sharding (path mode only — fold sweeps
    keep the full design, so cv rows report ``None``): each device holds
    one shard-width block, so the column grows ~linearly in the shard
    count."""
    from ..core.problem import Plan
    plan = plan or Plan()
    hbm = int(hbm_bytes or DEFAULT_BUDGETS["device_hbm_bytes"])
    rows = []
    for penalty in ("sgl", "nn_lasso"):
        for dtype in ("float32", "float64"):
            for mode in ("path", "cv"):
                kw = dict(plan=plan, hbm_bytes=hbm, N=N,
                          group_size=group_size)
                rows.append({
                    "penalty": penalty, "dtype": dtype, "mode": mode,
                    "max_p_screened": capacity_max_p(
                        penalty, dtype, mode, survivors=survivors, **kw),
                    "max_p_unscreened": capacity_max_p(
                        penalty, dtype, mode, survivors=None, **kw),
                    "max_p_sharded": (capacity_max_p(
                        penalty, dtype, mode, survivors=survivors,
                        feature_shards=feature_shards, **kw)
                        if mode == "path" and feature_shards > 1
                        else None),
                })
    return rows
