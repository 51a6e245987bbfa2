"""The repro-lint rule catalog (R001-R005).  See docs/dev.md.

R001  dispatch-bypass      direct ``repro.kernels.*`` imports outside
                           the dispatch/plan layers and kernel tests
R002  tracer-unsafe branch Python ``if``/``while`` on traced values
                           inside jit/plan-execute functions
R003  host-sync-in-hot-path  block_until_ready / device_get /
                           non-telemetry debug.callback inside plan
                           execute paths
R004  persisted-schema drift  sparse/spec.py + sparse/cache.py persisted
                           field lists vs the committed golden baseline
R005  nondeterministic benchmark  unseeded RNG / wall-clock outside the
                           measurement harness in benchmarks/
"""
from __future__ import annotations

import ast
import json
import os
from typing import Dict, List, Optional, Set

from tools.lint.engine import (FileContext, Finding, RepoRule, Rule,
                               register_rule)


def _attr_chain(node) -> Optional[str]:
    """Dotted name of a Name/Attribute chain ('jax.debug.callback'),
    or None when the chain bottoms out in a call/subscript."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _parent_map(root) -> Dict[ast.AST, ast.AST]:
    parents = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


# ---------------------------------------------------------------------------
# R001 dispatch-bypass
# ---------------------------------------------------------------------------

# the layers that legitimately enter kernels directly
_R001_ALLOWED_PREFIXES = ("src/repro/kernels/", "tools/lint/")
_R001_ALLOWED_FILES = {
    "src/repro/core/dispatch.py",
    "src/repro/sparse/plan.py",
    "tests/test_kernels.py",          # kernel conformance tests
    "tests/test_gmm_capacity.py",     # grouped-kernel capacity tests
    "tests/test_tpu_compile.py",      # Mosaic compiles of the wrappers
}
# the contract and the tile alignment are kernel *metadata*, not kernel
# entry points
_R001_EXEMPT_MODULES = ("repro.kernels.contract", "repro.kernels.tiling")


@register_rule
class DispatchBypass(Rule):
    id = "R001"
    name = "dispatch-bypass"
    description = ("kernels must be entered via core.dispatch / the plan "
                   "layer, not imported directly")

    def check(self, ctx: FileContext) -> List[Finding]:
        if (ctx.path in _R001_ALLOWED_FILES
                or ctx.path.startswith(_R001_ALLOWED_PREFIXES)):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            # the *effective* imported modules: "from repro.kernels
            # import contract" imports repro.kernels.contract, so the
            # exemptions must be checked per-alias, not on the bare
            # "from" module
            mods: List[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [f"{node.module}.{a.name}" for a in node.names]
            for mod in mods:
                if not (mod == "repro.kernels"
                        or mod.startswith("repro.kernels.")):
                    continue
                if mod.startswith(_R001_EXEMPT_MODULES):
                    continue
                out.append(Finding(
                    self.id, ctx.path, node.lineno,
                    f"direct kernel import {mod!r}: go through "
                    f"repro.core.dispatch or repro.sparse instead"))
                break
        return out


# ---------------------------------------------------------------------------
# jit-scope detection shared by R002/R003
# ---------------------------------------------------------------------------

# names of plan-execute closures: functions with these names *nested in
# another function* are the callables MatmulPlan jits / custom_vjp runs
_EXECUTE_CLOSURE_NAMES = {"run", "fwd", "bwd"}


def _jit_wrapped_names(tree) -> Set[str]:
    """Function names passed positionally to jax.jit(...) in this file."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain in ("jax.jit", "jit") and node.args:
                a = node.args[0]
                if isinstance(a, ast.Name):
                    out.add(a.id)
    return out


def _is_jit_decorated(fn) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        chain = _attr_chain(target) or ""
        leaf = chain.rsplit(".", 1)[-1]
        if leaf in ("jit", "custom_vjp", "custom_jvp"):
            return True
    return False


def jit_scoped_functions(ctx: FileContext):
    """Yield (FunctionDef, reason) for every function repro-lint treats
    as traced: jit/custom_vjp-decorated, passed to ``jax.jit(...)`` by
    name, or a plan-execute closure (a def named run/fwd/bwd nested
    inside another function -- methods and module-level defs excluded).
    """
    wrapped = _jit_wrapped_names(ctx.tree)
    parents = _parent_map(ctx.tree)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _is_jit_decorated(node):
            yield node, "jit/custom_vjp decorated"
        elif node.name in wrapped:
            yield node, "wrapped by jax.jit(...)"
        elif (node.name in _EXECUTE_CLOSURE_NAMES
              and isinstance(parents.get(node),
                             (ast.FunctionDef, ast.AsyncFunctionDef))):
            yield node, "plan-execute closure"


def _param_names(fn) -> Set[str]:
    a = fn.args
    names = [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n != "self"}


# attribute reads that stay static under tracing
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}


def _traced_value_uses(test, params: Set[str]) -> List[ast.Name]:
    """Name nodes in ``test`` that read a traced parameter's *value*
    (not a static property such as .shape/.ndim, isinstance, is-None)."""
    parents = _parent_map(test)
    parents[test] = None
    bad = []
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and node.id in params):
            continue
        p = parents.get(node)
        if isinstance(p, ast.Attribute) and p.attr in _STATIC_ATTRS:
            continue
        if isinstance(p, ast.Call):
            chain = _attr_chain(p.func) or ""
            if chain.rsplit(".", 1)[-1] in ("isinstance", "len", "type",
                                            "getattr", "hasattr"):
                continue
        if isinstance(p, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in p.ops):
            continue
        bad.append(node)
    return bad


# ---------------------------------------------------------------------------
# R002 tracer-unsafe branching
# ---------------------------------------------------------------------------

@register_rule
class TracerUnsafeBranch(Rule):
    id = "R002"
    name = "tracer-unsafe-branch"
    description = ("Python control flow on traced values inside "
                   "jit/plan-execute functions")

    def check(self, ctx: FileContext) -> List[Finding]:
        if not ctx.path.startswith("src/repro/"):
            return []
        out = []
        for fn, reason in jit_scoped_functions(ctx):
            params = _param_names(fn)
            if not params:
                continue
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    exprs = [node.test]
                elif isinstance(node, ast.Assert):
                    exprs = [node.test]
                else:
                    continue
                for expr in exprs:
                    for use in _traced_value_uses(expr, params):
                        out.append(Finding(
                            self.id, ctx.path, use.lineno,
                            f"branch on traced value {use.id!r} inside "
                            f"{fn.name!r} ({reason}): use lax.cond/"
                            f"jnp.where or hoist to plan time"))
        return out


# ---------------------------------------------------------------------------
# R003 host sync in hot path
# ---------------------------------------------------------------------------

@register_rule
class HostSyncInHotPath(Rule):
    id = "R003"
    name = "host-sync-in-hot-path"
    description = ("block_until_ready / device_get / non-telemetry "
                   "debug.callback inside plan execute paths")

    def check(self, ctx: FileContext) -> List[Finding]:
        if not ctx.path.startswith("src/repro/"):
            return []
        out = []
        for fn, reason in jit_scoped_functions(ctx):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = _attr_chain(node.func) or ""
                leaf = chain.rsplit(".", 1)[-1]
                if leaf == "block_until_ready":
                    out.append(Finding(
                        self.id, ctx.path, node.lineno,
                        f"block_until_ready inside {fn.name!r} "
                        f"({reason}): host sync in a hot path"))
                elif leaf == "device_get":
                    out.append(Finding(
                        self.id, ctx.path, node.lineno,
                        f"device_get inside {fn.name!r} ({reason}): "
                        f"host transfer in a hot path"))
                elif chain.endswith("debug.callback"):
                    # telemetry convention: CapacityStats.record sinks
                    # are the one sanctioned callback in execute paths
                    first = node.args[0] if node.args else None
                    is_telemetry = (isinstance(first, ast.Attribute)
                                    and first.attr == "record")
                    if not is_telemetry:
                        out.append(Finding(
                            self.id, ctx.path, node.lineno,
                            f"non-telemetry debug.callback inside "
                            f"{fn.name!r} ({reason})"))
        return out


# ---------------------------------------------------------------------------
# R004 persisted-schema drift
# ---------------------------------------------------------------------------

SPEC_PATH = "src/repro/sparse/spec.py"
CACHE_PATH = "src/repro/sparse/cache.py"
BASELINE_PATH = os.path.join(os.path.dirname(__file__),
                             "schema_baseline.json")
# the dataclasses whose fields reach the persisted decision records
_PERSISTED_CLASSES = ("OpSpec", "PlanContext", "CapacityStats")


def _class_fields(cls: ast.ClassDef) -> List[str]:
    """Field list of a persisted class: dataclass annotations plus
    ``self.x = ...`` assignments in ``__init__`` (public names only)."""
    fields = set()
    for stmt in cls.body:
        if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target,
                                                          ast.Name):
            fields.add(stmt.target.id)
        elif (isinstance(stmt, ast.FunctionDef)
              and stmt.name == "__init__"):
            for node in ast.walk(stmt):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                            fields.add(t.attr)
    return sorted(f for f in fields if not f.startswith("_"))


def compute_schema_fingerprint(repo_root: str = ".") -> dict:
    """Parse spec.py/cache.py and return the persisted-schema
    fingerprint {schema_version, fields: {class: [field, ...]}}."""
    with open(os.path.join(repo_root, SPEC_PATH)) as f:
        spec_tree = ast.parse(f.read())
    with open(os.path.join(repo_root, CACHE_PATH)) as f:
        cache_tree = ast.parse(f.read())
    version = None
    for node in ast.walk(cache_tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "SCHEMA_VERSION":
                    version = ast.literal_eval(node.value)
    fields = {}
    for node in spec_tree.body:
        if (isinstance(node, ast.ClassDef)
                and node.name in _PERSISTED_CLASSES):
            fields[node.name] = _class_fields(node)
    return {"schema_version": version, "fields": fields}


def _schema_version_line(repo_root: str) -> int:
    with open(os.path.join(repo_root, CACHE_PATH)) as f:
        for i, line in enumerate(f, 1):
            if line.startswith("SCHEMA_VERSION"):
                return i
    return 1


@register_rule
class PersistedSchemaDrift(RepoRule):
    id = "R004"
    name = "persisted-schema-drift"
    description = ("persisted dataclass fields changed without a "
                   "SCHEMA_VERSION bump + baseline update")

    def check_repo(self, files, repo_root: str) -> List[Finding]:
        # only meaningful when the persisted modules are in scope
        if not os.path.exists(os.path.join(repo_root, SPEC_PATH)):
            return []
        current = compute_schema_fingerprint(repo_root)
        line = _schema_version_line(repo_root)
        if not os.path.exists(BASELINE_PATH):
            return [Finding(
                self.id, CACHE_PATH, line,
                "missing persisted-schema baseline "
                "tools/lint/schema_baseline.json -- run "
                "`python -m tools.lint --update-baseline` and commit it")]
        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
        out = []
        same_version = (current["schema_version"]
                        == baseline.get("schema_version"))
        for cls in _PERSISTED_CLASSES:
            cur = current["fields"].get(cls, [])
            base = baseline.get("fields", {}).get(cls, [])
            if cur == base:
                continue
            added = sorted(set(cur) - set(base))
            removed = sorted(set(base) - set(cur))
            diff = "".join([f" +{f}" for f in added]
                           + [f" -{f}" for f in removed])
            if same_version:
                out.append(Finding(
                    self.id, SPEC_PATH, line,
                    f"persisted schema drift in {cls}:{diff} without a "
                    f"SCHEMA_VERSION bump (cache.py still "
                    f"{current['schema_version']}) -- bump it, then run "
                    f"`python -m tools.lint --update-baseline`"))
            else:
                out.append(Finding(
                    self.id, SPEC_PATH, line,
                    f"persisted schema changed in {cls}:{diff} and "
                    f"SCHEMA_VERSION bumped -- refresh the baseline with "
                    f"`python -m tools.lint --update-baseline`"))
        if not out and not same_version:
            out.append(Finding(
                self.id, CACHE_PATH, line,
                f"SCHEMA_VERSION {current['schema_version']} != baseline "
                f"{baseline.get('schema_version')} -- run "
                f"`python -m tools.lint --update-baseline`"))
        return out


# ---------------------------------------------------------------------------
# R005 nondeterministic benchmark code
# ---------------------------------------------------------------------------

# the one file allowed to read the wall clock: the measurement harness
_R005_HARNESS = "benchmarks/bench_walltime.py"
_WALLCLOCK_CHAINS = {"time.time", "time.monotonic", "time.time_ns",
                     "time.monotonic_ns", "datetime.now",
                     "datetime.datetime.now", "datetime.utcnow",
                     "datetime.datetime.utcnow"}
_GLOBAL_NP_RANDOM = {"rand", "randn", "randint", "random", "choice",
                     "permutation", "shuffle", "uniform", "normal",
                     "seed"}


@register_rule
class NondeterministicBenchmark(Rule):
    id = "R005"
    name = "nondeterministic-benchmark"
    description = ("unseeded RNG / wall-clock outside the measurement "
                   "harness in benchmark code")

    def check(self, ctx: FileContext) -> List[Finding]:
        if not ctx.path.startswith("benchmarks/"):
            return []
        imports_stdlib_random = any(
            isinstance(n, ast.Import)
            and any(a.name == "random" for a in n.names)
            for n in ast.walk(ctx.tree))
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func) or ""
            if chain in _WALLCLOCK_CHAINS:
                out.append(Finding(
                    self.id, ctx.path, node.lineno,
                    f"wall-clock {chain}() in benchmark code: route "
                    f"timing through the measurement harness"))
            elif chain == "time.perf_counter" and ctx.path != _R005_HARNESS:
                out.append(Finding(
                    self.id, ctx.path, node.lineno,
                    "perf_counter outside the measurement harness "
                    f"({_R005_HARNESS}): use measure_callable"))
            elif chain.endswith("random.default_rng") and not node.args:
                out.append(Finding(
                    self.id, ctx.path, node.lineno,
                    "unseeded default_rng(): pass an explicit seed"))
            elif (chain.startswith(("np.random.", "numpy.random."))
                  and chain.rsplit(".", 1)[-1] in _GLOBAL_NP_RANDOM):
                out.append(Finding(
                    self.id, ctx.path, node.lineno,
                    f"global numpy RNG {chain}(): use a seeded "
                    f"default_rng(seed) generator"))
            elif (imports_stdlib_random
                  and chain.startswith("random.")):
                out.append(Finding(
                    self.id, ctx.path, node.lineno,
                    f"stdlib global RNG {chain}(): use a seeded "
                    f"generator"))
        return out
