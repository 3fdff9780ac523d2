"""``repro.analysis`` — AST-based invariant linter for this codebase.

The last three PRs each paid a manual tax to the same bug classes:
jit-cache collisions (dt/policy-retune reusing stale compiled scans),
tracer leaks breaking the zero-perturbation ``observe=`` contract,
Pallas kernels silently closing over array constants, unbounded module
caches, and backend keyword surfaces drifting apart so a knob added to
one engine silently no-ops on another.  This package enforces those
invariants mechanically — the way an agile hardware flow relies on
automated design-rule checking rather than reviewer vigilance.

Rule passes (one module each under :mod:`repro.analysis.rules`):

=======  ==============================================================
RPR001   tracer leak: Python ``if``/``while``/``bool()``/``float()``/
         ``.item()``/``np.*`` applied to traced values inside functions
         reached by ``jax.jit`` / ``lax.scan`` / ``pallas_call``
RPR002   jit-cache-key completeness: hand-rolled jit caches must key on
         every non-tensor value baked into the traced closure
RPR003   unbounded caches: ``lru_cache(maxsize=None)``, ``@cache``,
         module/instance dict caches with inserts but no eviction
RPR004   dtype discipline: no f32 literals on the declared f64
         reference paths; no silent f64 upcasts on jax paths
RPR005   Pallas kernel rules: no array-valued closures, no ``np.*``
         calls, no Python branches on ref-derived values
RPR006   backend-surface parity: the engines' keyword surfaces for
         shared knobs agree or explicitly raise NotImplementedError
=======  ==============================================================

CLI::

    python -m repro.analysis [--format text|json] [--baseline FILE]
                             [--changed-only] [paths...]

Findings carry ``file:line``, rule id, rationale, and a stable
fingerprint.  Pre-existing accepted findings live in the checked-in
baseline (``analysis/baseline.json``) so they don't block CI while any
NEW finding fails it.  Inline suppression::

    offending_line  # repro: noqa[RPR003] justification text (required)

and an opt-in ``# repro: traced`` marker on a ``def`` line forces the
jit-boundary inference to treat that function as traced (for closures
handed across call boundaries the call-graph cannot follow).
"""
from repro.analysis.engine import (AnalysisReport, ModuleContext,
                                   analyze_paths, iter_python_files)
from repro.analysis.findings import (Finding, load_baseline, save_baseline)
from repro.analysis.rules import RULES, get_rules

__all__ = [
    "AnalysisReport", "ModuleContext", "analyze_paths",
    "iter_python_files", "Finding", "load_baseline", "save_baseline",
    "RULES", "get_rules",
]
