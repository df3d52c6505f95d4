"""Serialization, compat-routing and sync-hygiene passes.

- **serialization** — repo-wide ban on raw ``pickle.load(s)`` and
  ``np.load(allow_pickle=True)`` outside the restricted-unpickler homes
  (``registry.PICKLE_ALLOWED``): anything crossing a file/KV boundary is
  untrusted input and one raw load is a pickle-RCE door.
- **compat-routing** — device-only / version-mobile jax APIs
  (``registry.DEVICE_ONLY_APIS``) must be imported through
  ``h2o3_tpu/compat.py``, never directly: compat.py is written against
  the one pinned jax, so an upgrade is edited there and nowhere else.
- **sync-hygiene** — inside ``obs.tracing.span(...)``-instrumented
  blocks, device-sync-forcing calls (``np.asarray``/``np.array`` on
  device values, ``.block_until_ready()``, ``jax.device_get``,
  ``float()/int()`` on arrays) are flagged: a span that silently blocks
  turns the observability plane into a perf regression. Plus the
  swallowed-exception lint (``except: pass``) in the watchdog/supervisor
  tick paths — a silently-dead recovery loop is an outage multiplier.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from h2o3_tpu.analysis.core import Context, Finding
from h2o3_tpu.analysis.passes_mirrored import _dotted, _normalize

# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def run_serialization(ctx: Context) -> List[Finding]:
    """No module is exempt from the raw-load ban (zero raw loads exist
    after ISSUE 11, so an allowlist hole would only ever hide a NEW one).
    ``PICKLE_ALLOWED`` instead bounds where ``pickle.Unpickler``
    subclasses may be DEFINED — restricted unpicklers are a security
    surface and must not proliferate into bespoke per-module copies.
    Both call sites (``pickle.load(f)``) and bare references
    (``loads = loads or pickle.loads``) are findings."""
    allowed = tuple(ctx.reg("PICKLE_ALLOWED", ()))
    findings: List[Finding] = []
    for mod in ctx.project.modules.values():
        seen_lines = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.Attribute, ast.Name)):
                name = _normalize(_dotted(node), mod.imports) \
                    if isinstance(node, ast.Attribute) \
                    else mod.imports.get(node.id)
                if name in ("pickle.load", "pickle.loads") and \
                        node.lineno not in seen_lines:
                    seen_lines.add(node.lineno)
                    findings.append(ctx.finding(
                        "serialization", mod, node,
                        f"raw `{name}` on external bytes — route through "
                        f"the restricted unpickler (utils/unpickle.py); "
                        f"arbitrary pickles are remote code execution",
                        symbol=mod.rel))
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "allow_pickle" and \
                            isinstance(kw.value, ast.Constant) and \
                            kw.value.value is True:
                        findings.append(ctx.finding(
                            "serialization", mod, node,
                            "`allow_pickle=True` — npz/npy payloads must "
                            "stay pickle-free (allow_pickle=False is the "
                            "contract for every artifact surface)",
                            symbol=mod.rel))
            elif isinstance(node, ast.ClassDef) and not any(
                    mod.rel == a or mod.rel.startswith(a)
                    for a in allowed):
                for b in node.bases:
                    bname = _normalize(_dotted(b), mod.imports) or ""
                    if bname.endswith("Unpickler"):
                        findings.append(ctx.finding(
                            "serialization", mod, node,
                            f"Unpickler subclass `{node.name}` outside "
                            f"the sanctioned homes ({', '.join(allowed)})"
                            f" — extend utils/unpickle.py instead of "
                            f"forking the allowlist", symbol=mod.rel))
    return findings


# ---------------------------------------------------------------------------
# compat-routing
# ---------------------------------------------------------------------------

def _matches(name: str, key: str) -> bool:
    return name == key or name.startswith(key + ".")


def run_compat(ctx: Context) -> List[Finding]:
    apis = ctx.reg("DEVICE_ONLY_APIS", {})
    compat = ctx.reg("COMPAT_MODULE", "h2o3_tpu/compat.py")
    findings: List[Finding] = []
    for mod in ctx.project.modules.values():
        if mod.rel == compat or mod.rel.startswith("h2o3_genmodel/"):
            # the genmodel runners are framework-free by contract and run
            # exactly the exporter's program — compat shims live with the
            # framework, not in the standalone runtime
            continue
        seen_lines = set()

        def emit(node, api, how):
            if node.lineno in seen_lines:
                return
            seen_lines.add(node.lineno)
            findings.append(ctx.finding(
                "compat-routing", mod, node,
                f"direct {how} of `{api}` ({apis[api]}) — route through "
                f"h2o3_tpu/compat.py, the one module edited on a jax upgrade",
                symbol=mod.rel))

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    for api in apis:
                        if _matches(a.name, api):
                            emit(node, api, "import")
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                for api in apis:
                    if _matches(base, api):
                        emit(node, api, "import")
                        break
                else:
                    for a in node.names:
                        full = f"{base}.{a.name}"
                        for api in apis:
                            if _matches(full, api):
                                emit(node, api, "import")
            elif isinstance(node, ast.Attribute):
                name = _normalize(_dotted(node), mod.imports)
                if name:
                    for api in apis:
                        if _matches(name, api):
                            emit(node, api, "use")
    return findings


# ---------------------------------------------------------------------------
# compile-ledger
# ---------------------------------------------------------------------------

def run_compile_ledger(ctx: Context) -> List[Finding]:
    """Every XLA compile must route through ``obs/compiles.py`` (the
    ledger chokepoint, ``registry.COMPILE_LEDGER_MODULES``): a direct
    ``.lower(...).compile(`` — chained or via a name bound from a
    ``.lower(...)`` call — a direct ``compile_stablehlo`` call, or a
    direct ``note_compile`` call elsewhere is an unrecorded compile that
    silently under-counts /3/Runtime and the compile-seconds series."""
    allowed = set(ctx.reg("COMPILE_LEDGER_MODULES",
                          ("h2o3_tpu/obs/compiles.py",)))
    jit_scope = tuple(ctx.reg("JIT_LEDGER_SCOPE", ()))
    compat = ctx.reg("COMPAT_MODULE", "h2o3_tpu/compat.py")
    findings: List[Finding] = []
    for mod in ctx.project.modules.values():
        if mod.rel in allowed or mod.rel.startswith("h2o3_genmodel/"):
            # the genmodel runners are framework-free by contract (they
            # execute the exporter's exact program through the raw XLA
            # client); the ledger lives with the framework
            continue
        # names (incl. dotted attribute targets like `self._lowered`)
        # bound from a `.lower(...)` call anywhere in the module — the
        # two-step spelling: lowered = fn.lower(...); lowered.compile()
        lowered_names = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.Assign, ast.AnnAssign)) and \
                    isinstance(getattr(node, "value", None), ast.Call) and \
                    isinstance(node.value.func, ast.Attribute) and \
                    node.value.func.attr == "lower":
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    d = _dotted(t)
                    if d:
                        lowered_names.add(d)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "compile":
                direct = (isinstance(fn.value, ast.Call)
                          and isinstance(fn.value.func, ast.Attribute)
                          and fn.value.func.attr == "lower")
                via_name = (_dotted(fn.value) or "") in lowered_names
                if direct or via_name:
                    findings.append(ctx.finding(
                        "compile-ledger", mod, node,
                        "direct `.lower(...).compile(` — every XLA "
                        "compile must route through obs/compiles.py "
                        "(compile_jit/compile_lowered) so it lands a "
                        "ledger row on /3/Runtime", symbol=mod.rel))
            name = _dotted(fn)
            if name and name.split(".")[-1] == "compile_stablehlo" and \
                    mod.rel != compat:
                # the blessed wrapper IS the remediation — a call whose
                # base resolves to the ledger module must not be flagged
                norm = _normalize(name, mod.imports) or name
                via_ledger = (norm.startswith("h2o3_tpu.obs.compiles.")
                              or name.split(".")[-2:-1] == ["compiles"])
                if not via_ledger:
                    findings.append(ctx.finding(
                        "compile-ledger", mod, node,
                        "direct `compile_stablehlo` call — route through "
                        "obs/compiles.py compile_stablehlo(family, text) "
                        "so the compile is ledger-recorded",
                        symbol=mod.rel))
            if name and name.split(".")[-1] == "note_compile":
                findings.append(ctx.finding(
                    "compile-ledger", mod, node,
                    "direct `note_compile` call — the ledger is the one "
                    "writer of the fused-compile counter (it times the "
                    "compile itself, so compile_ms_total cannot drift "
                    "from the per-program rows)", symbol=mod.rel))
        # bare `jax.jit` ban inside the ledgered-jit scopes (ISSUE 17):
        # calls, decorators and bare references all resolve to the same
        # Attribute/Name node, so one walk catches every spelling
        if any(mod.rel.startswith(p) for p in jit_scope):
            seen_jit = set()
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Attribute):
                    name = _normalize(_dotted(node), mod.imports)
                elif isinstance(node, ast.Name):
                    name = mod.imports.get(node.id)
                else:
                    continue
                if name == "jax.jit" and node.lineno not in seen_jit:
                    seen_jit.add(node.lineno)
                    findings.append(ctx.finding(
                        "compile-ledger", mod, node,
                        "bare `jax.jit` in a ledgered-jit scope — use "
                        "obs/compiles.ledgered_jit(family, fn) so the "
                        "compiles this jit triggers land in the ledger "
                        "(family `tree` for models/tree/)",
                        symbol=mod.rel))
    # registry self-check: a renamed chokepoint must not turn this pass
    # into a green no-op
    for rel in allowed:
        if not any(m.rel == rel for m in ctx.project.modules.values()):
            findings.append(Finding(
                "compile-ledger", "h2o3_tpu/analysis/registry.py", 0,
                f"COMPILE_LEDGER_MODULES entry `{rel}` matches no module "
                f"— stale registry path; fix it", symbol=rel, snippet=rel))
    for prefix in jit_scope:
        if not any(m.rel.startswith(prefix)
                   for m in ctx.project.modules.values()):
            findings.append(Finding(
                "compile-ledger", "h2o3_tpu/analysis/registry.py", 0,
                f"JIT_LEDGER_SCOPE prefix `{prefix}` matches no module — "
                f"stale registry path; fix it", symbol=prefix,
                snippet=prefix))
    return findings


# ---------------------------------------------------------------------------
# sync-hygiene
# ---------------------------------------------------------------------------

_SYNC_CALLS = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
               "jax.device_get"}


def _is_span_with(node: ast.With, imports) -> bool:
    for item in node.items:
        ce = item.context_expr
        if isinstance(ce, ast.Call):
            name = _normalize(_dotted(ce.func), imports) or ""
            if name.endswith("tracing.span") or name.endswith(".span") \
                    and "tracing" in name:
                return True
            if name == "span" or name.endswith("obs.tracing.span"):
                return True
    return False


def run_sync_hygiene(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for mod in ctx.project.modules.values():
        if not mod.rel.startswith("h2o3_tpu/"):
            continue
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.With) and
                    _is_span_with(node, mod.imports)):
                continue
            # calls under a NESTED span belong to that span's own scan
            # (the module walk visits every With), so exclude their
            # subtrees here instead of double-attributing them
            nested: set = set()
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.With) and \
                            _is_span_with(sub, mod.imports):
                        for inner in ast.walk(sub):
                            if inner is not sub:
                                nested.add(id(inner))
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if id(sub) in nested or not isinstance(sub, ast.Call):
                        continue
                    name = _normalize(_dotted(sub.func), mod.imports)
                    if name in _SYNC_CALLS:
                        findings.append(ctx.finding(
                            "sync-hygiene", mod, sub,
                            f"`{name}` inside a tracing span forces a "
                            f"device sync under instrumentation — move it "
                            f"out, or baseline it with the audit note if "
                            f"the span deliberately measures the blocking "
                            f"transfer", symbol=mod.rel))
                    elif isinstance(sub.func, ast.Attribute) and \
                            sub.func.attr == "block_until_ready":
                        findings.append(ctx.finding(
                            "sync-hygiene", mod, sub,
                            "`block_until_ready()` inside a tracing span "
                            "— instrumentation must not add device "
                            "syncs", symbol=mod.rel))
                    elif isinstance(sub.func, ast.Name) and \
                            sub.func.id in ("float", "int") and \
                            len(sub.args) == 1 and not sub.keywords and \
                            isinstance(sub.args[0], (ast.Attribute,
                                                     ast.Subscript)):
                        findings.append(ctx.finding(
                            "sync-hygiene", mod, sub,
                            f"`{sub.func.id}(...)` on an array-like "
                            f"inside a tracing span blocks on the device "
                            f"value", symbol=mod.rel))
    # swallowed exceptions on recovery tick paths
    for rel in ctx.reg("SWALLOW_SCOPE", ()):
        mod = next((m for m in ctx.project.modules.values()
                    if m.rel == rel), None)
        if mod is None:
            # registry self-check: a renamed tick module must not
            # silently drop out of the swallow lint
            findings.append(Finding(
                "sync-hygiene", "h2o3_tpu/analysis/registry.py", 0,
                f"SWALLOW_SCOPE entry `{rel}` matches no module — stale "
                f"registry path; fix it", symbol=rel, snippet=rel))
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ExceptHandler) and \
                    len(node.body) == 1 and \
                    isinstance(node.body[0], ast.Pass):
                findings.append(ctx.finding(
                    "sync-hygiene", mod, node,
                    "swallowed exception (`except: pass`) on a recovery "
                    "tick path — a permanently-failing tick dies "
                    "silently; log it at debug at minimum",
                    symbol=mod.rel))
    return findings
