"""Registry passes: the four text guards folded in from the consistency
suite (ISSUE 11 satellite) so there is ONE invariant engine.

- **faultpoints** — every faultpoint a test arms must exist in source (a
  renamed faultpoint silently defuses its chaos tests);
- **metric-registry** — metric names unique, ``^h2o3_[a-z0-9_]+$``, and
  at least the promised series count (the live-registry agreement half
  stays a behavioral test);
- **timeline-kinds** — every recorded timeline kind is declared in
  ``utils/timeline.py KINDS`` and no declared kind is dead;
- **knob-docs** — every ``H2O_TPU_*`` env knob read in source is
  documented in README.md.
"""

from __future__ import annotations

import ast
import re
from collections import Counter
from typing import List

from h2o3_tpu.analysis.core import Context, Finding

_MIN_METRICS = 20

# the one source-scan pattern for metric registrations — the live-registry
# behavioral test (tests/test_consistency.py) reuses it so the two halves
# of the guard can never drift apart
METRIC_REG_PAT = re.compile(
    r"\br\.(?:counter|gauge|histogram)(?:_fn)?\(\s*['\"]([^'\"]+)['\"]")


def _src_texts(ctx: Context):
    for mod in ctx.project.modules.values():
        if mod.rel.startswith("h2o3_tpu/"):
            yield mod


def _test_texts(ctx: Context, exclude=()):
    if ctx.tests_dir is None:
        return
    for p in sorted(ctx.tests_dir.rglob("*.py")):
        if "__pycache__" in p.parts:
            continue
        rel = p.relative_to(ctx.root).as_posix()
        if rel in exclude:
            continue
        yield rel, p.read_text(encoding="utf-8", errors="replace")


def run_faultpoints(ctx: Context) -> List[Finding]:
    defined = set()
    for mod in _src_texts(ctx):
        defined |= set(re.findall(
            r"faultpoint\(\s*['\"]([^'\"]+)['\"]", mod.text))
    exclude = ctx.reg("FAULTPOINT_SCAN_EXCLUDE", ())
    armed = {}
    for rel, text in _test_texts(ctx, exclude):
        for name in re.findall(r"\binject\(\s*['\"]([^'\"]+)['\"]", text):
            armed.setdefault(name, rel)
        for name in re.findall(r"_FAULTS\[\s*['\"]([^'\"]+)['\"]\s*\]",
                               text):
            armed.setdefault(name, rel)
        # mechanism self-tests define throwaway faultpoints inline
        defined |= set(re.findall(r"faultpoint\(\s*['\"]([^'\"]+)['\"]",
                                  text))
    return [Finding("faultpoints", rel, 0,
                    f"test arms faultpoint `{name}` that no longer exists "
                    f"in h2o3_tpu/ — a renamed faultpoint silently "
                    f"defuses its chaos tests", symbol=name, snippet=name)
            for name, rel in sorted(armed.items()) if name not in defined]


def run_metric_registry(ctx: Context) -> List[Finding]:
    names: Counter = Counter()
    where = {}
    for mod in _src_texts(ctx):
        for m in METRIC_REG_PAT.finditer(mod.text):
            names[m.group(1)] += 1
            where.setdefault(m.group(1), mod.rel)
    findings: List[Finding] = []
    if not names:
        findings.append(Finding("metric-registry", "h2o3_tpu/", 0,
                                "no metric registrations found",
                                snippet="none"))
        return findings
    for n in sorted(names):
        if not re.match(r"^h2o3_[a-z0-9_]+$", n):
            findings.append(Finding(
                "metric-registry", where[n], 0,
                f"metric name `{n}` does not match ^h2o3_[a-z0-9_]+$ — "
                f"Prometheus scrapes reject it", symbol=n, snippet=n))
        if names[n] > 1:
            findings.append(Finding(
                "metric-registry", where[n], 0,
                f"metric `{n}` registered {names[n]} times — the registry "
                f"raises on the second registration", symbol=n,
                snippet=n))
    if len(names) < _MIN_METRICS:
        findings.append(Finding(
            "metric-registry", "h2o3_tpu/obs/metrics.py", 0,
            f"only {len(names)} metrics registered — /3/Metrics promises "
            f">= {_MIN_METRICS} series", snippet="count"))
    return findings


def _declared_kinds(ctx: Context) -> set:
    mod = ctx.project.modules.get("h2o3_tpu.utils.timeline")
    if mod is None:
        return set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "KINDS":
            return {n.value for n in ast.walk(node.value)
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, str)}
    return set()


def run_timeline_kinds(ctx: Context) -> List[Finding]:
    declared = _declared_kinds(ctx)
    call_pat = re.compile(
        r"\btimeline\.record\(\s*['\"]([^'\"]+)['\"]")
    bare_pat = re.compile(r"(?<![\w.])record\(\s*['\"]([^'\"]+)['\"]")
    used = {}
    for mod in _src_texts(ctx):
        for m in call_pat.finditer(mod.text):
            used.setdefault(m.group(1), mod.rel)
        if mod.rel.endswith("utils/timeline.py"):
            for m in bare_pat.finditer(mod.text):
                used.setdefault(m.group(1), mod.rel)
    findings = [Finding("timeline-kinds", rel, 0,
                        f"timeline kind `{k}` is recorded but not "
                        f"declared in utils/timeline.py KINDS (the "
                        f"enumeration is the ring's query surface)",
                        symbol=k, snippet=k)
                for k, rel in sorted(used.items()) if k not in declared]
    for k in sorted(declared - set(used) - {"rest"}):
        findings.append(Finding(
            "timeline-kinds", "h2o3_tpu/utils/timeline.py", 0,
            f"timeline kind `{k}` is declared in KINDS but never "
            f"recorded — drop it or record it", symbol=k, snippet=k))
    findings.extend(_phase_name_findings(ctx))
    return findings


def _declared_phases(ctx: Context) -> set:
    mod = ctx.project.modules.get("h2o3_tpu.obs.phases")
    if mod is None:
        return set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "PHASES":
            return {n.value for n in ast.walk(node.value)
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, str)}
    return set()


def _phase_name_findings(ctx: Context) -> List[Finding]:
    """The lifecycle-phase enumeration half of the timeline-kinds guard
    (ISSUE 12): every phase literal passed to obs.phases ``enter`` must
    be declared in ``obs/phases.py PHASES``, and every declared phase
    must be entered somewhere — a dead phase name makes /3/Runtime's
    table lie."""
    declared = _declared_phases(ctx)
    enter_pat = re.compile(r"\bphases\.enter\(\s*['\"]([^'\"]+)['\"]")
    used = {}
    for mod in _src_texts(ctx):
        for m in enter_pat.finditer(mod.text):
            used.setdefault(m.group(1), mod.rel)
    if not declared and not used:
        # synthetic fixture projects without a phase tracker have
        # nothing to guard; a real repo that renamed obs/phases.py but
        # kept enter() calls still gets findings below
        return []
    findings = [Finding(
        "timeline-kinds", rel, 0,
        f"lifecycle phase `{p}` is entered but not declared in "
        f"obs/phases.py PHASES (closed enumeration)", symbol=p, snippet=p)
        for p, rel in sorted(used.items()) if p not in declared]
    for p in sorted(declared - set(used)):
        findings.append(Finding(
            "timeline-kinds", "h2o3_tpu/obs/phases.py", 0,
            f"lifecycle phase `{p}` is declared in PHASES but never "
            f"entered — drop it or wrap its boot step", symbol=p,
            snippet=p))
    return findings


def run_knob_docs(ctx: Context) -> List[Finding]:
    used = {}
    for mod in _src_texts(ctx):
        for m in re.finditer(r"\bH2O_TPU_[A-Z0-9_]+\b", mod.text):
            used.setdefault(m.group(0), mod.rel)
    readme = ctx.root / "README.md"
    documented = set()
    if readme.is_file():
        documented = set(re.findall(
            r"\bH2O_TPU_[A-Z0-9_]+\b",
            readme.read_text(encoding="utf-8", errors="replace")))
    return [Finding("knob-docs", rel, 0,
                    f"env knob `{k}` is read in h2o3_tpu/ but not "
                    f"documented in README.md — operators discover knobs "
                    f"there, not by grepping source", symbol=k, snippet=k)
            for k, rel in sorted(used.items()) if k not in documented]
