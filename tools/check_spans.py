#!/usr/bin/env python
"""Static span- and metric-registry checker.

Three contracts guard the telemetry subsystem's honesty, and all are
checkable without running anything:

1. REGISTRY COVERAGE — every span name used in the package (a string or
   f-string literal passed to `trace_region(...)` / `span(...)`) must
   match a pattern declared in `telemetry.spans.DECLARED_SPANS`. A
   typo'd region name would otherwise silently fork a new time series
   (and, under `amg.*`, silently leak out of the accounted fraction).

2. LEAF DISJOINTNESS — the declared patterns under the accounted prefix
   (`amg.*`) must be pairwise NON-NESTING: `profiling.timers_total`
   sums them flat, so a declared span that is an ancestor of another
   declared span would double-count its child's wall time and the PR-3
   `setup_accounted_fraction >= 0.9` contract would silently report
   fractions > honest.

3. METRIC-NAME COVERAGE — every literal metric name recorded through
   the registry (`_tm.inc(...)` / `_tm.add(...)` /
   `metrics.observe(...)` / `set_gauge` / `max_gauge` on the package's
   conventional receivers)
   must be declared in the matching catalog
   (telemetry.metrics.COUNTERS / GAUGES / HISTOGRAMS). The registry
   raises at runtime too, but only when the line executes — this
   catches the typo'd counter in the error path nobody exercised.
   Non-literal names (the serving cache's configurable counter map)
   are skipped: the runtime check owns those.

4. NO DEAD METRICS — the REVERSE of 3: every name in the DECLARED
   catalogs must have at least one recording site in the package — a
   literal receiver call, an f-string receiver call whose wildcard
   pattern covers it (`_tm.inc(f"resilience.fallback.{action}")`
   keeps the whole family alive), or a plain string constant equal to
   the name (the indirected counter maps the serving cache threads
   through). Docstrings don't count. Catches catalog rot: a metric
   whose last increment site was refactored away would otherwise keep
   being exported as an eternally-zero series that LOOKS like
   instrumentation.

f-string placeholders (`{expr}`) are normalized to `*`, so
`f"amg.L{k}.galerkin"` checks as `amg.L*.galerkin`. Calls whose name is
not a literal cannot be checked statically and are reported (there are
deliberately none in the package).

Exit code 0 = clean; 1 = violations (printed one per line). Wired into
the test suite by tests/test_telemetry.py.
"""
from __future__ import annotations

import ast
import fnmatch
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

PKG = os.path.join(_ROOT, "amgx_tpu")

# the recording engine itself (generic `name` parameters, the decorator
# helper): it defines the machinery, it does not USE span names
_EXEMPT = (
    os.path.join("amgx_tpu", "profiling.py"),
    os.path.join("amgx_tpu", "telemetry", "spans.py"),
)

# _tspan/_tmark are the serving layer's knob-gated wrappers; their
# call sites carry the literal lifecycle names (the wrappers' own
# forwarding bodies use the checker-invisible _raw aliases, like the
# engine in the exempt spans.py)
_CALL_NAMES = {"trace_region", "span", "mark", "record_span",
               "_tspan", "_tmark"}

# metric-recording surface: attribute calls on the package's
# conventional registry receivers (`_tm.inc(...)`, `metrics.observe`).
# Receiver-qualified on purpose: other objects legitimately own methods
# with these names (determinism.DeterminismChecker.observe)
_METRIC_RECEIVERS = {"_tm", "metrics", "_metrics"}
_METRIC_KINDS = {"inc": "counter", "add": "counter", "set_gauge": "gauge",
                 "max_gauge": "gauge", "observe": "histogram",
                 "quantile": "histogram"}
_METRIC_EXEMPT = (
    os.path.join("amgx_tpu", "telemetry", "metrics.py"),
)


def _call_name(node: ast.Call):
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _normalize(node):
    """A Call's first argument as a wildcard pattern: plain string
    literals pass through, f-string placeholders become '*', anything
    else returns None (not statically checkable)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:                       # FormattedValue
                parts.append("*")
        return "".join(parts)
    return None


def extract_span_literals(root: str = PKG):
    """(file, line, normalized_name) for every span-name use; name is
    None for calls whose argument is not a (f-)string literal. AST-
    based, so docstrings and comments never false-positive."""
    out = []
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, _ROOT)
            if rel in _EXEMPT:
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call) \
                        or _call_name(node) not in _CALL_NAMES \
                        or not node.args:
                    continue
                out.append((path, node.lineno, _normalize(node.args[0])))
    return out


def extract_metric_literals(root: str = PKG):
    """(file, line, kind, name) for every literal metric name recorded
    through the registry's conventional receivers. Dynamic names
    (variables threaded through a config map) are skipped — the
    runtime registry's did-you-mean raise owns those."""
    return _extract_metric_calls(root)[0]


# the RECORDING half of the receiver surface (quantile is a read —
# contract 3 checks its name, contract 4 must not count it as a site)
_WRITE_ATTRS = {"inc", "add", "set_gauge", "max_gauge", "observe"}


def _extract_metric_calls(root: str = PKG):
    """(literals, patterns, writes): literal receiver-call names as
    before; the f-string WRITE calls normalized to wildcard patterns
    (`f"resilience.fallback.{action}"` -> 'resilience.fallback.*');
    and the (kind, name) literal WRITE sites — contract 4's evidence
    that a metric (family) has a live recording site."""
    literals, patterns, writes = [], [], []
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, _ROOT)
            if rel in _METRIC_EXEMPT:
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                f_ = node.func
                if not (isinstance(f_, ast.Attribute)
                        and f_.attr in _METRIC_KINDS
                        and isinstance(f_.value, ast.Name)
                        and f_.value.id in _METRIC_RECEIVERS):
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str):
                    literals.append((path, node.lineno,
                                     _METRIC_KINDS[f_.attr], arg.value))
                    if f_.attr in _WRITE_ATTRS:
                        writes.append((_METRIC_KINDS[f_.attr],
                                       arg.value))
                elif isinstance(arg, ast.JoinedStr) \
                        and f_.attr in _WRITE_ATTRS:
                    pat = _normalize(arg)
                    if pat is not None:
                        patterns.append((path, node.lineno,
                                         _METRIC_KINDS[f_.attr], pat))
    return literals, patterns, writes


def extract_string_constants(root: str = PKG):
    """Every non-docstring string constant in the package — contract
    4's fallback evidence for metric names threaded through
    indirection (the serving cache's counter map). Exact-equality
    matching only, so a name mentioned inside a prose sentence never
    counts."""
    out = set()
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, _ROOT)
            if rel in _METRIC_EXEMPT:
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            docstrings = set()
            for node in ast.walk(tree):
                if isinstance(node, (ast.Module, ast.ClassDef,
                                     ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    body = getattr(node, "body", [])
                    if body and isinstance(body[0], ast.Expr) \
                            and isinstance(body[0].value, ast.Constant) \
                            and isinstance(body[0].value.value, str):
                        docstrings.add(id(body[0].value))
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and id(node) not in docstrings:
                    out.add(node.value)
    return out


def _compatible(used: str, declared: str) -> bool:
    """Could the used (possibly wildcarded) name match the declared
    pattern? A used '*' is an f-string placeholder — a solver name or
    a level index, assumed DOT-FREE (every placeholder in the package
    substitutes an identifier/number), so segment counts must agree
    and comparison is per dot-segment. The used name's LITERAL
    segments and the literal prefix/suffix around its placeholders
    must fit the declared pattern exactly — a typo in any literal part
    ('*.solv', 'amg.L*.stregth') fails against every declared entry.
    Exact fnmatch for the fully-literal case."""
    if "*" not in used:
        return fnmatch.fnmatchcase(used, declared)
    us, ds = used.split("."), declared.split(".")
    if len(us) != len(ds):
        return False            # placeholders never contain dots
    for u, d in zip(us, ds):
        if "*" in u:
            # unknown placeholder content: compatible when the
            # declared segment is itself a wildcard, or the used
            # segment's literal prefix/suffix around '*' fits the
            # declared literal
            if "*" in d:
                continue
            pre, _, suf = u.partition("*")
            if not (d.startswith(pre) and d.endswith(suf)):
                return False
        elif not fnmatch.fnmatchcase(u, d):
            return False
    return True


def check():
    from amgx_tpu.telemetry import spans as S

    errors = []

    # 1. registry coverage
    for path, line, name in extract_span_literals():
        rel = os.path.relpath(path, _ROOT)
        if name is None:
            errors.append(f"{rel}:{line}: span name is not a string "
                          f"literal (cannot be checked statically)")
            continue
        if not any(_compatible(name, d) for d in S.DECLARED_SPANS):
            errors.append(f"{rel}:{line}: span {name!r} matches no "
                          f"declared pattern (telemetry/spans.py "
                          f"DECLARED_SPANS)")

    # 2. accounted-leaf disjointness: concretize '*' and require that
    # no declared amg.* pattern is a dotted ancestor of another
    acc = [d for d in S.DECLARED_SPANS
           if d.startswith(S.ACCOUNTED_PREFIX)]
    conc = {d: d.replace("*", "X") for d in acc}
    for a in acc:
        for b in acc:
            if a != b and conc[b].startswith(conc[a] + "."):
                errors.append(
                    f"declared span {a!r} is an ancestor of {b!r}: "
                    f"the accounted amg.* sum would double-count")

    # 3. metric-name coverage: literal names recorded through the
    # registry must be declared in the matching catalog
    from amgx_tpu.telemetry import metrics as M
    catalogs = {"counter": M.COUNTERS, "gauge": M.GAUGES,
                "histogram": M.HISTOGRAMS}
    literals, patterns, writes = _extract_metric_calls()
    for path, line, kind, name in literals:
        rel = os.path.relpath(path, _ROOT)
        if name not in catalogs[kind]:
            errors.append(
                f"{rel}:{line}: {kind} {name!r} is not declared in "
                f"telemetry/metrics.py "
                f"({'COUNTERS' if kind == 'counter' else 'GAUGES' if kind == 'gauge' else 'HISTOGRAMS'})")

    # 4. no dead metrics: every declared name needs a recording site —
    # a literal call of the right WRITE kind, an f-string call whose
    # wildcard covers it, or (indirection fallback) an exact string
    # constant anywhere outside a docstring. `quantile` is a read, not
    # a recording site.
    write_kinds = {"counter", "gauge", "histogram"}
    lit_by_kind = {k: set() for k in write_kinds}
    for kind, name in writes:
        lit_by_kind[kind].add(name)
    pat_by_kind = {k: set() for k in write_kinds}
    for path, line, kind, pat in patterns:
        pat_by_kind[kind].add(pat)
    constants = None      # lazily built: most names resolve earlier
    for kind, catalog in catalogs.items():
        for name in catalog:
            if name in lit_by_kind[kind]:
                continue
            if any(fnmatch.fnmatchcase(name, p)
                   for p in pat_by_kind[kind]):
                continue
            if constants is None:
                constants = extract_string_constants()
            if name in constants:
                continue
            errors.append(
                f"dead metric: declared {kind} {name!r} has no "
                f"increment/observe site in the package (catalog rot "
                f"— remove the declaration or restore the "
                f"instrumentation)")
    return errors


def main() -> int:
    errors = check()
    if errors:
        for e in errors:
            print(e)
        print(f"check_spans: {len(errors)} violation(s)")
        return 1
    print("check_spans: OK (span-registry coverage + accounted-leaf "
          "disjointness + metric-name coverage + no dead metrics)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
