"""repro.analysis — static analysis suite for the TLFre engine.

Four layers prove at trace/parse time what ``EngineStats`` counters only
observe at runtime:

  1. ``jaxpr_lint``    — dtype purity, hidden transfers, GEMM counts in
     the traced graphs of every jitted entry point.
  2. ``compile_audit`` + ``pallas_check`` — the O(log p) compile-key
     universe of a Problem/Plan, and BlockSpec/ragged-mask/f64 contracts
     of every Pallas kernel.
  3. ``ast_rules``     — jit-boundary hazards in the host driver code.
  4. ``resource_audit`` — per-compile-key cost cards (peak HBM envelope,
     loop-expanded FLOPs/bytes, per-launch transfer, shard_map collective
     plan + layout divisibility), gated on ``analysis/budgets.json``.

CLI::

    PYTHONPATH=src python -m repro.analysis --all \
        --baseline analysis/baseline.json --budgets analysis/budgets.json

x64 is enabled at import: the f64 exactness contract can only be checked
if f64 traces are actually f64 (and ``GroupSpec.weights`` master data is
f64), regardless of how the host process was configured.  Import this
package before creating jax arrays whose dtype matters.
"""
from __future__ import annotations

import jax

jax.config.update("jax_enable_x64", True)

from .findings import (Finding, diff_against_baseline, format_report,  # noqa: E402
                       load_baseline, write_baseline)

LAYERS = ("jaxpr", "compile", "pallas", "ast", "resource")

#: every rule id a layer can emit — baseline entries citing a rule outside
#: this registry are definitionally rot (the rule no longer exists) and
#: fail the CLI instead of warning
KNOWN_RULES = (
    "jaxpr/upcast-in-loop", "jaxpr/f64-downcast", "jaxpr/accum-downcast",
    "jaxpr/transfer-in-loop", "jaxpr/full-gemm-count",
    "jaxpr/pallas-on-f64",
    "compile/budget-exceeded", "compile/unpredicted-key",
    "pallas/tpu-tiling",
    "pallas/mask-coverage", "pallas/f64-aval", "pallas/f64-gate",
    "pallas/no-kernel",
    "ast/host-sync-in-traced", "ast/host-sync-in-hot-loop",
    "ast/jit-dispatch-in-loop", "ast/tracer-branch",
    "ast/block-until-ready", "ast/deprecated-shim",
    "resource/hbm-over-budget", "resource/unexpected-collective",
    "resource/non-divisible-shard",
    "resource/transfer-in-segment-regression",
)


def run_layers(layers=LAYERS, budgets=None) -> list:
    """Run the requested analyzer layers; returns all findings.
    ``budgets`` (path) feeds the resource layer's ``analysis/budgets.json``
    gate; the other layers ignore it."""
    findings = []
    if "jaxpr" in layers:
        from . import jaxpr_lint
        findings.extend(jaxpr_lint.run())
    if "compile" in layers:
        from . import compile_audit
        findings.extend(compile_audit.run())
    if "pallas" in layers:
        from . import pallas_check
        findings.extend(pallas_check.run())
    if "ast" in layers:
        from . import ast_rules
        findings.extend(ast_rules.run())
    if "resource" in layers:
        from . import resource_audit
        findings.extend(resource_audit.run(budgets=budgets))
    return findings


__all__ = ["Finding", "KNOWN_RULES", "LAYERS", "diff_against_baseline",
           "format_report", "load_baseline", "run_layers",
           "write_baseline"]
