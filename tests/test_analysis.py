"""Tier-1 tests for the ``repro.analysis`` static-analysis suite.

Two contracts are enforced here:

  1. The *repository* is clean under every analyzer layer (modulo the
     committed ``analysis/baseline.json``).  In particular the f64
     exactness contract — no downcasts, no kernels reachable — is now a
     STATIC property of the traced jaxprs, not just a runtime counter
     (``test_f64_fold_paths_never_engage_kernels`` keeps the one runtime
     ``n_pallas_screens == 0`` check).
  2. The *analyzers themselves* catch seeded violations: a deliberate
     upcast inside a scan, a host transfer mid-scan, a non-divisible
     BlockSpec, a float64 kernel aval, and the full set of AST hazards —
     while clean code produces zero findings.
"""
import os
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import diff_against_baseline, load_baseline
from repro.analysis import ast_rules, compile_audit, jaxpr_lint, pallas_check
from repro.core.problem import Plan, Problem
from repro.core.session import SGLSession

_BASELINE = os.path.join(os.path.dirname(__file__), os.pardir,
                         "analysis", "baseline.json")


# ---------------------------------------------------------------------------
# 1. The repository is clean under every layer
# ---------------------------------------------------------------------------

def test_jaxpr_f64_purity_static():
    """Static replacement of the runtime f64-purity checks: every engine /
    CV / fold / serve entry point traced at float64 shows zero findings —
    no narrowing converts, no pallas_call reachable, no transfers in scan
    bodies, exactly one full-X GEMM per certification row."""
    assert jaxpr_lint.run(dtypes=("float64",)) == []


def test_jaxpr_f32_no_hot_loop_upcasts():
    """f32 traces of the same entries never promote to f64 inside a
    scan/while body (the classic leak: float64 GroupSpec.weights reaching
    the FISTA prox)."""
    assert jaxpr_lint.run(dtypes=("float32",)) == []


def test_compile_audit_repo_clean():
    assert compile_audit.run() == []


@pytest.mark.pallas
def test_pallas_check_repo_clean():
    """BlockSpec divisibility, lane alignment, f64 avals, poisoned-padding
    mask coverage, and the f64 TypeError gate — all kernels clean."""
    assert pallas_check.run() == []


def test_ast_rules_match_baseline():
    """AST findings on the tree equal the committed baseline exactly: no
    new jit-boundary hazards, and no stale (already-fixed) entries left to
    rot in the baseline."""
    findings = ast_rules.run()
    new, _, stale = diff_against_baseline(findings, load_baseline(_BASELINE))
    assert new == []
    assert stale == []


# ---------------------------------------------------------------------------
# 2. Seeded violations — each layer must catch its fixture
# ---------------------------------------------------------------------------

def test_seeded_f64_downcast_is_caught():
    def bad(x):
        return jnp.sum(x.astype(jnp.float32))

    x = jnp.ones(5, jnp.float64)
    found = jaxpr_lint.lint_traceable(bad, x, name="seeded", dtype="float64")
    assert [f.rule for f in found] == ["jaxpr/f64-downcast"]


def test_seeded_upcast_in_scan_is_caught():
    w64 = jnp.ones(5, jnp.float64)

    def bad(x):
        def body(c, xi):
            return c + jnp.sum(xi * w64).astype(x.dtype), None
        return jax.lax.scan(body, jnp.zeros((), x.dtype), x)[0]

    x = jnp.ones((3, 5), jnp.float32)
    found = jaxpr_lint.lint_traceable(bad, x, name="seeded", dtype="float32")
    assert "jaxpr/upcast-in-loop" in [f.rule for f in found]


def test_seeded_transfer_in_scan_is_caught():
    def bad(x):
        def body(c, xi):
            r = jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((), x.dtype), xi)
            return c + r, None
        return jax.lax.scan(body, jnp.zeros((), x.dtype), x)[0]

    x = jnp.ones(4, jnp.float32)
    found = jaxpr_lint.lint_traceable(bad, x, name="seeded", dtype="float32")
    assert "jaxpr/transfer-in-loop" in [f.rule for f in found]


def test_seeded_upcast_in_loss_closure_is_caught():
    """A loss whose methods silently compute in f64 upcasts the f32 hot
    loop through the Loss indirection — the lint must see through the
    closure exactly as it sees a bare constant (the loss-generic refactor
    must not open a purity blind spot)."""
    import functools
    from repro.core.groups import GroupSpec
    from repro.core.losses import LogisticLoss
    from repro.core.solver import fista_sgl

    class _LeakyLogistic(LogisticLoss):
        def grad(self, y, u):
            return (jax.nn.sigmoid(u.astype(jnp.float64))
                    - y.astype(jnp.float64)).astype(u.dtype)

    rng = np.random.default_rng(0)
    spec = GroupSpec.from_sizes([3, 2, 5])
    X = jnp.asarray(rng.standard_normal((8, 10)), jnp.float32)
    y = jnp.asarray((rng.standard_normal(8) > 0), jnp.float32)
    fn = functools.partial(fista_sgl, max_iter=40, check_every=10,
                           tol=1e-6, loss=_LeakyLogistic())
    found = jaxpr_lint.lint_traceable(
        fn, X, y, spec, 0.5, 0.9, jnp.asarray(4.0, jnp.float32),
        jnp.zeros(10, jnp.float32), name="seeded-loss", dtype="float32")
    assert "jaxpr/upcast-in-loop" in [f.rule for f in found]
    # the honest singleton is clean on the same trace
    honest = functools.partial(fista_sgl, max_iter=40, check_every=10,
                               tol=1e-6, loss=LogisticLoss())
    clean = jaxpr_lint.lint_traceable(
        honest, X, y, spec, 0.5, 0.9, jnp.asarray(4.0, jnp.float32),
        jnp.zeros(10, jnp.float32), name="clean-loss", dtype="float32")
    assert clean == []


def test_clean_scan_has_no_findings():
    def good(x):
        def body(c, xi):
            return c + jnp.sum(xi), None
        return jax.lax.scan(body, jnp.zeros((), x.dtype), x)[0]

    for dt in ("float32", "float64"):
        x = jnp.ones((3, 5), jnp.dtype(dt))
        assert jaxpr_lint.lint_traceable(good, x, name="clean",
                                         dtype=dt) == []


@pytest.mark.pallas
def test_seeded_bad_blockspec_is_caught():
    import jax.experimental.pallas as pl

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        # block 5 over a dim of 7: interpret mode takes it, Mosaic refuses
        # a sublane block that is neither a multiple of 8 nor the whole dim
        return pl.pallas_call(
            copy_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(2,),
            in_specs=[pl.BlockSpec((5, x.shape[1]), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((5, x.shape[1]), lambda i: (i, 0)),
        )(x)

    x = jnp.ones((7, 128), jnp.float32)
    found = pallas_check.check_traceable(bad, x, name="seeded")
    assert "pallas/tpu-tiling" in [f.rule for f in found]


@pytest.mark.pallas
def test_seeded_f64_aval_is_caught():
    import jax.experimental.pallas as pl

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        return pl.pallas_call(
            copy_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(2,),
            in_specs=[pl.BlockSpec((4, x.shape[1]), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, x.shape[1]), lambda i: (i, 0)),
        )(x)

    x = jnp.ones((8, 128), jnp.float64)
    found = pallas_check.check_traceable(bad, x, name="seeded")
    assert "pallas/f64-aval" in [f.rule for f in found]


_AST_BAD = textwrap.dedent("""
    import numpy as np
    import jax

    def traced_fn(x, flag):
        v = float(x.sum())
        if flag:
            x = x + 1
        return x * v

    def hot_driver(X):
        total = 0.0
        res = None
        for i in range(3):
            res = solve_sgl(X)
            total += float(res)
        out = jax.block_until_ready(res)
        return total, out
""")

_AST_CLEAN = textwrap.dedent("""
    import numpy as np

    def traced_ok(x, y=None, *, screen="dpc", max_iter=100):
        if y is not None:
            x = x + y
        if screen == "gapsafe":
            x = x * 2
        return x

    def host_ok(grid):
        total = 0.0
        for lam in grid:
            total += lam           # plain host floats, no device values
        return total
""")


def test_seeded_ast_hazards_are_caught():
    found = ast_rules.lint_source(
        _AST_BAD, "core/fixture.py",
        traced={"core/fixture.py": {"traced_fn"}},
        hot={"core/fixture.py": {"hot_driver"}})
    rules = {f.rule for f in found}
    assert rules == {
        "ast/host-sync-in-traced",      # float() inside the traced fn
        "ast/tracer-branch",            # if flag: on a traced param
        "ast/jit-dispatch-in-loop",     # solve_sgl() per iteration
        "ast/host-sync-in-hot-loop",    # float(res) on a device value
        "ast/block-until-ready",        # unsanctioned barrier
    }


def test_clean_ast_has_no_findings():
    found = ast_rules.lint_source(
        _AST_CLEAN, "core/fixture.py",
        traced={"core/fixture.py": {"traced_ok"}},
        hot={"core/fixture.py": {"host_ok"}})
    assert found == []


# ---------------------------------------------------------------------------
# 3. Compile-key audit agrees with a real engine run
# ---------------------------------------------------------------------------

def _small_sgl_problem():
    rng = np.random.default_rng(0)
    N, p = 30, 48
    X = rng.standard_normal((N, p))
    beta = np.zeros(p)
    beta[:6] = rng.standard_normal(6)
    y = X @ beta + 0.05 * rng.standard_normal(N)
    return Problem.sgl(X, y, groups=[4] * 12)


def test_compile_keys_all_predicted():
    """Every compile key a real session pays (path + cv on one problem)
    is a member of the statically predicted universe, the session counter
    agrees with the cache, and the universe respects the polylog budget."""
    prob = _small_sgl_problem()
    plan = Plan(n_lambdas=12, n_folds=3, tol=1e-6, max_iter=2000)
    sess = SGLSession(prob, plan)
    sess.path()
    sess.cv()

    shape = compile_audit.ProblemShape.of(prob)
    universe = compile_audit.predict_keys(shape, plan, kinds=("path", "cv"),
                                          n_folds=3)
    assert compile_audit.verify_paid_keys(sess.compile_keys, universe) == []
    assert sess.stats.n_compilations == len(sess.compile_keys)
    assert len(universe) <= compile_audit.budget(shape, plan, n_folds=3)
    assert compile_audit.audit(shape, plan, n_folds=3) == []


def test_unpredicted_key_is_flagged():
    prob = _small_sgl_problem()
    plan = Plan(n_lambdas=12, n_folds=3)
    universe = compile_audit.predict_keys(
        compile_audit.ProblemShape.of(prob), plan, n_folds=3)
    bogus = ("sgl", 30, 48, 12, "float64", 1, 1, False, 48, 12, 4, 1)
    found = compile_audit.verify_paid_keys([bogus], universe)
    assert [f.rule for f in found] == ["compile/unpredicted-key"]


# ---------------------------------------------------------------------------
# 4. Resource audit (Layer 4): cost cards, budget rules, shard layout
# ---------------------------------------------------------------------------

from repro.analysis import resource_audit  # noqa: E402
from repro.launch.mesh import (abstract_fold_mesh,  # noqa: E402
                               fold_shard_compatible, shard_over_folds)

_BUDGETS = os.path.join(os.path.dirname(__file__), os.pardir,
                        "analysis", "budgets.json")


def test_resource_audit_repo_clean():
    """The representative configurations all fit the committed budgets:
    under HBM, collective-free sweep bodies, divisible layouts, transfer
    within the per-configuration envelope — zero findings."""
    assert resource_audit.run(budgets=_BUDGETS) == []


def test_seeded_oversized_bucket_breaches_hbm():
    """A bucket ladder blown up to p_b = p = 2^26 at f64 prices far beyond
    16 GB; exactly the hbm-over-budget rule fires."""
    key = ("sgl", 1000, 1 << 26, 1 << 22, "float64", 1000, 10, False,
           1 << 26, (1 << 22) + 1, 16, 64)
    card = resource_audit.card_for_key(key, "seeded-oversize")
    assert card.peak_bytes > resource_audit.DEFAULT_BUDGETS[
        "device_hbm_bytes"]
    found = resource_audit.check_cards([card],
                                       resource_audit.DEFAULT_BUDGETS)
    assert [f.rule for f in found] == ["resource/hbm-over-budget"]


def test_seeded_non_divisible_shard_is_caught():
    """A 4-device fold mesh over a 5-fold cohort degrades to single-shard
    vmap — the layout verifier flags exactly that."""
    found = resource_audit.verify_shard_layout(4, 5, "seeded-layout")
    assert [f.rule for f in found] == ["resource/non-divisible-shard"]
    assert resource_audit.verify_shard_layout(4, 8, "ok-layout") == []
    assert resource_audit.verify_shard_layout(1, 5, "single") == []


def test_seeded_collective_in_sweep_body_is_caught():
    """A psum smuggled into a fold-sharded body shows up in the extracted
    collective plan and trips unexpected-collective (unless the budget
    explicitly allows it)."""
    mesh = abstract_fold_mesh(2)

    def leaky(v):                       # (4, 8) rows, cross-fold reduction
        return v - jax.lax.psum(v.sum(), "fold")

    sharded = shard_over_folds(leaky, mesh, (0,))
    closed = jax.make_jaxpr(sharded)(
        jax.ShapeDtypeStruct((4, 8), jnp.float32))
    cost = resource_audit.walk_cost(closed.jaxpr, 1.0, 1)
    assert "psum" in cost["collectives"]
    assert cost["collectives"]["psum"]["count"] == 1

    card = resource_audit.card_for_key(
        ("nn-folds", 4, 20, 40, "float32", 100, 10, None, 16, 4, False),
        "seeded-collective")
    card = __import__("dataclasses").replace(
        card, collectives=cost["collectives"])
    found = resource_audit.check_cards([card],
                                       resource_audit.DEFAULT_BUDGETS)
    assert [f.rule for f in found] == ["resource/unexpected-collective"]
    allowed = dict(resource_audit.DEFAULT_BUDGETS,
                   allowed_collectives=["psum"])
    assert resource_audit.check_cards([card], allowed) == []


def test_seeded_transfer_regression_is_caught():
    """Tightening a configuration's transfer budget below the card's
    per-launch bytes fires transfer-in-segment-regression — the static
    tripwire for re-shipping a full-p operand every segment."""
    key = ("nn", 50, 200, "float64", 100, 10, False, 64, 8)
    card = resource_audit.card_for_key(key, "seeded-transfer")
    budgets = dict(resource_audit.DEFAULT_BUDGETS)
    budgets["configs"] = {"seeded-transfer":
                          {"peak_bytes": card.peak_bytes,
                           "transfer_bytes": card.transfer_bytes // 2}}
    found = resource_audit.check_cards([card], budgets)
    assert [f.rule for f in found] == [
        "resource/transfer-in-segment-regression"]
    budgets["configs"]["seeded-transfer"]["transfer_bytes"] = \
        card.transfer_bytes
    assert resource_audit.check_cards([card], budgets) == []


def test_fold_sweep_collective_plan_is_empty():
    """The engine's own fold sweeps are embarrassingly parallel: tracing
    the dominating cv keys under shard_map on an abstract 2-shard mesh
    extracts an EMPTY collective plan."""
    from repro.core.problem import Plan as _Plan
    plan = _Plan(n_lambdas=12, n_folds=4)
    shape = compile_audit.ProblemShape(N=40, p=96, G=24, max_size=4,
                                       penalty="sgl", dtype="float64")
    key = resource_audit.dominating_key(shape, plan, "cv", n_folds=4)
    assert resource_audit.fold_collective_plan(key, mesh_size=2) == {}


def test_peak_envelope_never_underestimates_xla():
    """The soundness contract behind every capacity/budget number: for a
    real audit card, XLA's own buffer-assignment peak never exceeds the
    static envelope, and the loop-expanded FLOPs dominate XLA's
    single-count figure."""
    from repro.launch import hlo_analysis
    key = ("sgl", 60, 128, 32, "float64", 200, 10, False, 64, 33, 4, 8)
    card = resource_audit.card_for_key(key, "soundness")
    compiled = resource_audit.compile_key(key)
    summary = hlo_analysis.compiled_summary(compiled)
    assert summary["memory"]["peak_bytes"] <= card.peak_bytes
    xla_flops = summary["raw_cost"].get("flops", 0.0)
    assert card.flops >= xla_flops


def test_capacity_planner_monotone_and_positive():
    """--capacity numbers behave like capacities: every cell is positive,
    screened >= unscreened for the same cell, f32 >= f64, and doubling
    HBM does not shrink max p."""
    from repro.core.problem import Plan as _Plan
    plan = _Plan(n_lambdas=12, n_folds=4)
    kw = dict(plan=plan, N=200, group_size=8, survivors=1024)
    small = resource_audit.capacity_max_p(
        "sgl", "float64", "path", hbm_bytes=int(2e9), **kw)
    big = resource_audit.capacity_max_p(
        "sgl", "float64", "path", hbm_bytes=int(4e9), **kw)
    f32 = resource_audit.capacity_max_p(
        "sgl", "float32", "path", hbm_bytes=int(2e9), **kw)
    unscreened = resource_audit.capacity_max_p(
        "sgl", "float64", "path", hbm_bytes=int(2e9),
        plan=plan, N=200, group_size=8, survivors=None)
    assert 0 < small <= big
    assert f32 >= small
    assert small >= unscreened > 0
    peak = resource_audit._peak_at(small, "sgl", "float64", "path",
                                   N=200, group_size=8, plan=plan,
                                   survivors=1024)
    assert peak <= int(2e9)


def test_capacity_searches_downward_when_first_probe_over():
    """A tiny HBM budget puts the planner's opening probe over budget; it
    must walk down and still return the largest fitting p instead of 0."""
    from repro.core.problem import Plan as _Plan
    plan = _Plan(n_lambdas=12, n_folds=4)
    got = resource_audit.capacity_max_p(
        "nn_lasso", "float64", "path", plan=plan, hbm_bytes=int(2e8),
        N=200, group_size=8, survivors=4096)
    assert got > 0
    peak = resource_audit._peak_at(got, "nn_lasso", "float64", "path",
                                   N=200, group_size=8, plan=plan,
                                   survivors=4096)
    assert peak <= int(2e8)


# ---------------------------------------------------------------------------
# 5. Mesh helpers the shard verifier builds on
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, size):
        self.size = size


@pytest.mark.parametrize("size,n_folds,want", [
    (1, 4, False),     # single device: never shard
    (2, 4, True),
    (2, 5, False),     # 5 folds over 2 shards: uneven split
    (4, 8, True),
    (4, 6, False),
    (3, 9, True),
])
def test_fold_shard_compatible_divisibility(size, n_folds, want):
    assert fold_shard_compatible(_FakeMesh(size), n_folds) is want
    assert fold_shard_compatible(None, n_folds) is False


def test_shard_over_folds_identity_on_single_device():
    fn = lambda v: v * 2  # noqa: E731
    assert shard_over_folds(fn, None, (0,)) is fn
    assert shard_over_folds(fn, _FakeMesh(1), (0,)) is fn


def test_shard_over_folds_abstract_trace_matches_vmap():
    """Traced under shard_map on an abstract 2-shard mesh, a fold-batched
    function keeps its global output shapes and introduces no
    collectives — the property the Layer-4 collective extractor relies
    on."""
    mesh = abstract_fold_mesh(2)
    assert mesh.size == 2

    def body(v, w):
        return v @ w, v.sum(axis=1)

    S = jax.ShapeDtypeStruct
    args = (S((4, 6, 3), jnp.float32), S((3, 5), jnp.float32))
    plain = jax.eval_shape(body, *args)
    sharded = shard_over_folds(body, mesh, (0, None))
    closed = jax.make_jaxpr(sharded)(*args)
    got = [v.aval for v in closed.jaxpr.outvars]
    want = jax.tree_util.tree_leaves(plain)
    assert [(v.shape, v.dtype) for v in got] == \
        [(w.shape, w.dtype) for w in want]
    cost = resource_audit.walk_cost(closed.jaxpr, 1.0, 1)
    assert cost["collectives"] == {}


# ---------------------------------------------------------------------------
# 6. Feature-sharded screening: collective plan + 2-D mesh banding (PR 9)
# ---------------------------------------------------------------------------

def _feat_key(penalty="sgl"):
    plan = Plan(n_lambdas=12, feature_shards=8)
    if penalty == "sgl":
        shape = compile_audit.ProblemShape(N=40, p=96, G=24, max_size=4,
                                           penalty="sgl", dtype="float64")
    else:
        shape = compile_audit.ProblemShape(N=40, p=96, G=0, max_size=0,
                                           penalty="nn_lasso",
                                           dtype="float64")
    return resource_audit.dominating_key(shape, plan, "path")


@pytest.mark.parametrize("penalty", ["sgl", "nn_lasso"])
def test_feature_collective_plan_is_psum_only(penalty):
    """AbstractMesh snapshot of the sharded screen+cert+fit composite:
    the plan is EXACTLY one psum — the (N,)-payload partial-fit
    reduction — and in particular contains no all_gather of X blocks
    (which would erase the memory win sharding exists for)."""
    key = _feat_key(penalty)
    assert key[0].endswith("-feat") and key[1] == 8
    plan_c = resource_audit.feature_collective_plan(key)
    assert set(plan_c) == {"psum"}
    assert plan_c["psum"]["count"] == 1
    assert plan_c["psum"]["payload_bytes"] == 40 * 8   # one (N,) f64 fit
    # degenerate 1-shard key: no mesh, no collectives
    one = (key[0], 1) + key[2:]
    assert resource_audit.feature_collective_plan(one) == {}


def test_feature_collective_plan_rejects_unsharded_keys():
    key = ("sgl", 40, 96, 24, "float64", 100, 10, False, 96, 25, 4, 8)
    with pytest.raises(ValueError):
        resource_audit.feature_collective_plan(key)


def test_seeded_gathering_screen_is_caught():
    """A sharded screen that all-gathers the full X onto every device is
    the violation the psum-only budget exists to catch: the extractor
    sees the gather, and check_cards fires unexpected-collective even
    though the config explicitly allows psum."""
    key = _feat_key("sgl")

    def leaky_screen(ops, Xs, specs, y, alpha, lams, theta, nvec, coln,
                     gspec):
        def body(Xb):
            full = jax.lax.all_gather(Xb, "feature")   # (S, N, p_sh)
            return full.sum(axis=(0, 1))               # on EVERY device
        return ops.fmap(body, Xs)

    plan_c = resource_audit.feature_collective_plan(key,
                                                    screen_fn=leaky_screen)
    assert "all_gather" in plan_c and "psum" in plan_c

    card = resource_audit.card_for_key(key, "seeded-gather")
    card = __import__("dataclasses").replace(card, collectives=plan_c)
    budgets = dict(resource_audit.DEFAULT_BUDGETS)
    budgets["configs"] = {"seeded-gather":
                          {"peak_bytes": card.peak_bytes,
                           "transfer_bytes": card.transfer_bytes,
                           "allowed_collectives": ["psum"]}}
    found = resource_audit.check_cards([card], budgets)
    assert [f.rule for f in found] == ["resource/unexpected-collective"]
    assert "all_gather" in found[0].detail
    # the engine's own plan passes under the same psum-only entry
    clean = __import__("dataclasses").replace(
        card, collectives=resource_audit.feature_collective_plan(key))
    assert resource_audit.check_cards([clean], budgets) == []


def test_feat_compile_keys_predicted_and_paid():
    """A sharded session pays only keys the static audit predicted, and
    the universe stays within the (doubled) polylog budget."""
    prob = _small_sgl_problem()
    plan = Plan(n_lambdas=12, tol=1e-6, max_iter=2000, feature_shards=8)
    sess = SGLSession(prob, plan)
    sess.path()
    shape = compile_audit.ProblemShape.of(prob)
    universe = compile_audit.predict_keys(shape, plan, kinds=("path",))
    assert any(k[0] == "sgl-feat" for k in sess.compile_keys)
    assert compile_audit.verify_paid_keys(sess.compile_keys, universe) == []
    assert len(universe) <= compile_audit.budget(shape, plan,
                                                 kinds=("path",))


class _FakeMesh2D:
    """Test double for a 2-D folds x features mesh (shape dict + size)."""
    def __init__(self, fold, feature):
        self.shape = {"fold": fold, "feature": feature}
        self.size = fold * feature


@pytest.mark.parametrize("n_folds,want", [(2, True), (3, False),
                                          (4, True), (8, True)])
def test_fold_shard_compatible_on_2d_mesh(n_folds, want):
    """Regression: on a 2x4 folds x features mesh only the fold-axis
    size (2) gates cohort banding — a K=3 cohort must fall back to vmap,
    while K=2/4/8 shard; demanding divisibility by all 8 devices would
    wrongly reject every one of them."""
    mesh = _FakeMesh2D(2, 4)
    assert fold_shard_compatible(mesh, n_folds) is want
    # a pure feature mesh (fold axis 1) never shards the fold rows
    assert fold_shard_compatible(_FakeMesh2D(1, 8), n_folds) is False
