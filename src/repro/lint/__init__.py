"""repro.lint — jit-aware static analysis for the TC-MIS codebase.

Six PRs of hot-path invariants ("packed stays packed", "host-silent round
loop", "one unpack at the epilogue") used to live in five ad-hoc AST guards
scoped by *directory* (tools/ci_guards.py).  This package replaces them with
a real analysis pass (DESIGN.md §15):

  * a rule engine — per-rule IDs (RPR0xx), severities, inline suppressions
    (`# repro-lint: disable=RPR0xx <reason>`), a checked-in baseline for
    grandfathered findings, and text/JSON/SARIF emitters so CI renders
    findings as GitHub annotations;
  * an interprocedural hot-path reachability analysis: the call graph is
    seeded at the jitted entry points (`_tc_mis_impl`,
    engine `step*` bodies, Pallas `*_kernel` functions, `repair_mis`) and
    the hot-path rules apply to every statically reachable function,
    regardless of which module it lives in — a host sync smuggled in via a
    helper imported into the round body no longer sails through;
  * a rule catalog: the five CI guards ported one-to-one (RPR001–RPR005)
    plus jax/pallas-specific rules — host-sync detection, trace impurity,
    dtype discipline, loop-carry hygiene, hot-path densify, deprecation
    enforcement and Pallas-kernel hygiene (RPR010–RPR016).

Run `python -m repro.lint src/` (exit 0 = clean); `tools/ci_guards.py`
survives as a thin shim that runs only the guard rules.
"""
from repro.lint.analysis import LintContext, load_universe
from repro.lint.baseline import Baseline
from repro.lint.callgraph import CallGraph, DEFAULT_SEEDS
from repro.lint.cli import main
from repro.lint.model import Finding, Rule, Severity
from repro.lint.rules import ALL_RULES, get_rules, run_rules

__all__ = [
    "ALL_RULES",
    "Baseline",
    "CallGraph",
    "DEFAULT_SEEDS",
    "Finding",
    "LintContext",
    "Rule",
    "Severity",
    "get_rules",
    "load_universe",
    "main",
    "run_rules",
]
