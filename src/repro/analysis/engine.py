"""AST lint engine for the repo's JAX/Pallas correctness contracts.

The blocking engine's correctness rests on exact bit-level contracts
(packed 62-bit sort words, splitmix64 owner routing, XOR fingerprints)
and its speed on hot paths that never silently fall off-device. Both are
enforced dynamically by parity tests and the ``--transfer-guard`` pytest
mode; this module enforces them *statically*, before the code runs:

- ``ModuleContext`` parses one file and resolves the import aliases,
  function table, jit/pallas/shard_map roots and the jit-reachable call
  closure that every rule keys off.
- ``analyze_paths`` runs in two phases: phase 1 parses every file and
  builds the whole-program index (``project.Project``: cross-module
  symbol table, call graph, jit reachability closure, mesh-axis
  universe); phase 2 runs the rule pack per module, so R001/R003
  reachability follows calls across module boundaries.
- Rules live in ``rules.py`` / ``rules_contracts.py`` and register
  themselves via ``register``; each is a pure function
  ``ModuleContext -> list[Finding]``.
- ``# repro: noqa[R001]`` (or bare ``# repro: noqa``) on the finding's
  line — or on the FIRST line of the multi-line statement containing
  it — suppresses it; suppressed findings are counted, not fatal.
- Phase-2 results are cached on disk keyed by (mtime, size) of the file
  plus a digest of the engine version, the rule selection, and the
  cross-module facts the file's findings depend on (``AnalysisCache``),
  so repeated CI/lint runs only re-check what changed.
- ``python -m repro.analysis PATH...`` walks files/trees and exits
  nonzero on any unsuppressed finding (the CI lint gate);
  ``--format github`` emits workflow annotations and ``--warn-only``
  reports without failing (the tests/ advisory lane).

Type inference remains a local-dataflow heuristic and call resolution
skips dynamic dispatch. Rules therefore aim to be *precise on this
codebase's idioms* and suppressible where intent is explicit, not sound
in general — see docs/ANALYSIS.md for each rule's exact contract.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
import re
import sys
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set

NOQA_RE = re.compile(r"#\s*repro:\s*noqa(?:\[(?P<rules>[A-Za-z0-9_,\s]*)\])?")

# bump when rules/engine change enough to invalidate cached findings
ANALYSIS_VERSION = "2"

# annotations the codebase uses for host-static (non-traced) parameters
_STATIC_ANNOTATIONS = {"int", "bool", "str", "float"}
# host objects passed into traced functions by convention (mesh handles
# are compile-time metadata: .shape/.axis_names reads are static)
_STATIC_OBJECT_TAILS = {"Mesh"}
# container annotations that are static when their elements are
_STATIC_CONTAINERS = {"Sequence", "Tuple", "List", "tuple", "list",
                      "Iterable", "FrozenSet", "frozenset"}
# attribute reads on traced arrays that yield host-static values
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False

    def format(self) -> str:
        mark = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col + 1}: {self.rule} {self.message}{mark}"


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    description: str
    check: Callable[["ModuleContext"], List[Finding]]


_REGISTRY: Dict[str, Rule] = {}


def register(rule_id: str, name: str, description: str):
    """Decorator: add a ``ModuleContext -> [Finding]`` function to the registry."""

    def deco(fn):
        _REGISTRY[rule_id] = Rule(rule_id, name, description, fn)
        return fn

    return deco


def all_rules() -> Dict[str, Rule]:
    # import for side effect: rules register on first use
    from . import rules, rules_contracts  # noqa: F401

    return dict(sorted(_REGISTRY.items()))


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chain -> "a.b.c", else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ModuleContext:
    """Everything the rules need to know about one parsed source file."""

    def __init__(self, path: str, src: str, tree: ast.Module):
        self.path = path
        self.src = src
        self.lines = src.splitlines()
        self.tree = tree

        # import alias tables (alias name -> stands for module X)
        self.numpy_aliases: Set[str] = set()
        self.jnp_aliases: Set[str] = set()
        self.jax_aliases: Set[str] = set()
        self.lax_aliases: Set[str] = set()
        self.pallas_aliases: Set[str] = set()
        self.time_aliases: Set[str] = set()
        self.functools_aliases: Set[str] = set()
        # names bound by from-imports
        self.jit_names: Set[str] = set()         # from jax import jit
        self.partial_names: Set[str] = set()     # from functools import partial
        self.cache_deco_names: Set[str] = set()  # lru_cache / cache
        self.perf_counter_names: Set[str] = set()
        self.shard_map_names: Set[str] = set()   # from jax import shard_map
        self.pallas_call_names: Set[str] = set()
        self.imports_jaxlike = False             # jax / jnp / repro imported

        # function table: name -> def node (module functions + methods;
        # later definitions win, matching runtime rebinding)
        self.functions: Dict[str, ast.AST] = {}
        # per-function host-static parameter names
        self.static_params: Dict[str, Set[str]] = {}
        self.jit_roots: Set[str] = set()
        self.jit_reachable: Set[str] = set()
        # set by project.Project after the phase-1 index is built; rules
        # may consult it for project-wide facts (None in single-file use)
        self.project = None
        # parent links for ancestry queries (loops, enclosing defs)
        self.parents: Dict[ast.AST, ast.AST] = {}

        self._collect_imports()
        self._collect_functions()
        self._collect_parents()
        self._collect_jit_roots()
        self._close_reachability()

    # -- construction --------------------------------------------------

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    bound = alias.asname or alias.name.split(".")[0]
                    if alias.name == "numpy":
                        self.numpy_aliases.add(bound)
                    elif alias.name == "jax.numpy" and alias.asname:
                        self.jnp_aliases.add(alias.asname)
                        self.imports_jaxlike = True
                    elif alias.name == "jax.lax" and alias.asname:
                        self.lax_aliases.add(alias.asname)
                        self.imports_jaxlike = True
                    elif alias.name.split(".")[0] == "jax":
                        self.jax_aliases.add(bound)
                        self.imports_jaxlike = True
                    elif alias.name == "time":
                        self.time_aliases.add(bound)
                    elif alias.name == "functools":
                        self.functools_aliases.add(bound)
                    elif alias.name.split(".")[0] == "repro":
                        self.imports_jaxlike = True
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if node.level > 0 or mod.split(".")[0] == "repro":
                    self.imports_jaxlike = True
                for alias in node.names:
                    bound = alias.asname or alias.name
                    if mod == "jax" and alias.name == "jit":
                        self.jit_names.add(bound)
                        self.imports_jaxlike = True
                    elif mod == "jax" and alias.name == "numpy":
                        self.jnp_aliases.add(bound)
                        self.imports_jaxlike = True
                    elif mod == "jax" and alias.name == "lax":
                        self.lax_aliases.add(bound)
                        self.imports_jaxlike = True
                    elif mod.split(".")[0] == "jax":
                        self.imports_jaxlike = True
                        if alias.name == "pallas":
                            self.pallas_aliases.add(bound)
                        elif alias.name == "pallas_call":
                            self.pallas_call_names.add(bound)
                        elif alias.name == "shard_map":
                            self.shard_map_names.add(bound)
                    elif mod == "functools":
                        if alias.name == "partial":
                            self.partial_names.add(bound)
                        elif alias.name in ("lru_cache", "cache"):
                            self.cache_deco_names.add(bound)
                    elif mod == "time" and alias.name == "perf_counter":
                        self.perf_counter_names.add(bound)

    def _collect_functions(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
                self.static_params[node.name] = self._annotation_static_params(node)

    def _collect_parents(self) -> None:
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    def _annotation_static_params(self, fn) -> Set[str]:
        static = set()
        args = list(fn.args.posonlyargs) + list(fn.args.args) + list(fn.args.kwonlyargs)
        for a in args:
            if self._is_static_annotation(a.annotation):
                static.add(a.arg)
        return static

    def _is_static_annotation(self, ann: Optional[ast.AST]) -> bool:
        """Does this annotation denote a host-static (untraced) value?

        int/bool/str annotations, the repo's frozen *Config dataclasses,
        mesh handles (compile-time metadata), and containers of static
        elements (``Sequence[str]``, ``Tuple[int, ...]``) are hashable
        static args by convention.
        """
        if ann is None:
            return False
        if isinstance(ann, ast.Subscript):
            base = dotted_name(ann.value)
            if base is None or base.split(".")[-1] not in _STATIC_CONTAINERS:
                return False
            sl = ann.slice
            elts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
            return all(
                (isinstance(e, ast.Constant) and e.value is Ellipsis)
                or self._is_static_annotation(e)
                for e in elts
            )
        name = None
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            name = ann.value.split("[")[0]
        else:
            name = dotted_name(ann)
        if name is None:
            return False
        tail = name.split(".")[-1]
        return (tail in _STATIC_ANNOTATIONS or tail.endswith("Config")
                or tail in _STATIC_OBJECT_TAILS)

    # -- jit root discovery --------------------------------------------

    def is_jit_expr(self, node: ast.AST) -> bool:
        """Does this expression denote ``jax.jit`` (or a bare ``jit``)?"""
        if isinstance(node, ast.Name) and node.id in self.jit_names:
            return True
        d = dotted_name(node)
        return bool(d) and any(d == f"{a}.jit" for a in self.jax_aliases)

    def is_partial_expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name) and node.id in self.partial_names:
            return True
        d = dotted_name(node)
        return bool(d) and any(d == f"{a}.partial" for a in self.functools_aliases)

    def is_tracing_wrapper(self, node: ast.AST) -> bool:
        """shard_map / pallas_call / vmap: wraps a traced function."""
        if isinstance(node, ast.Name) and (
            node.id in self.shard_map_names or node.id in self.pallas_call_names
        ):
            return True
        d = dotted_name(node)
        if not d:
            return False
        if any(d == f"{a}.pallas_call" for a in self.pallas_aliases):
            return True
        return any(
            d in (f"{a}.vmap", f"{a}.shard_map",
                  f"{a}.experimental.shard_map.shard_map")
            for a in self.jax_aliases
        )

    def _named_targets(self, call: ast.Call) -> Iterable[str]:
        """Local function names a jit/shard_map/pallas_call call wraps."""
        cands = list(call.args[:1]) + [
            kw.value for kw in call.keywords if kw.arg in ("fun", "kernel", "f")
        ]
        for arg in cands:
            # unwrap functools.partial(fn, ...) one level
            if isinstance(arg, ast.Call) and self.is_partial_expr(arg.func) and arg.args:
                arg = arg.args[0]
            if isinstance(arg, ast.Name):
                yield arg.id
            elif isinstance(arg, ast.Lambda):
                # lambdas trace inline: their body is scanned by rules via
                # the enclosing jit-reachable function, nothing to name
                continue

    def _static_argnames_from_call(self, call: ast.Call, fn) -> Set[str]:
        static: Set[str] = set()
        params = [a.arg for a in list(fn.args.posonlyargs) + list(fn.args.args)] if fn else []
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                vals = kw.value.elts if isinstance(kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(v.value, str):
                        static.add(v.value)
            elif kw.arg == "static_argnums":
                vals = kw.value.elts if isinstance(kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(v.value, int):
                        if 0 <= v.value < len(params):
                            static.add(params[v.value])
        return static

    def _collect_jit_roots(self) -> None:
        # decorator forms
        for name, fn in self.functions.items():
            for dec in fn.decorator_list:
                if self.is_jit_expr(dec):
                    self.jit_roots.add(name)
                elif isinstance(dec, ast.Call):
                    if self.is_jit_expr(dec.func):
                        self.jit_roots.add(name)
                        self.static_params[name] |= self._static_argnames_from_call(dec, fn)
                    elif (self.is_partial_expr(dec.func) and dec.args
                          and self.is_jit_expr(dec.args[0])):
                        self.jit_roots.add(name)
                        self.static_params[name] |= self._static_argnames_from_call(dec, fn)
        # call forms: jax.jit(f), shard_map(f, ...), pl.pallas_call(kernel, ...)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if self.is_jit_expr(node.func) or self.is_tracing_wrapper(node.func):
                for name in self._named_targets(node):
                    if name in self.functions:
                        self.jit_roots.add(name)
                        if self.is_jit_expr(node.func):
                            self.static_params[name] |= self._static_argnames_from_call(
                                node, self.functions[name]
                            )

    def _called_local_names(self, fn) -> Set[str]:
        called: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name):
                    called.add(node.func.id)
                elif (
                    isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"
                ):
                    called.add(node.func.attr)
            # bare references (fn passed as value, e.g. into lax.cond/scan)
            elif isinstance(node, ast.Name) and node.id in self.functions:
                called.add(node.id)
        return called

    def _close_reachability(self) -> None:
        reach = set(self.jit_roots)
        frontier = list(reach)
        while frontier:
            fn_name = frontier.pop()
            fn = self.functions.get(fn_name)
            if fn is None:
                continue
            for callee in self._called_local_names(fn):
                if callee in self.functions and callee not in reach:
                    reach.add(callee)
                    frontier.append(callee)
        self.jit_reachable = reach

    def extend_jit_reachable(self, names: Iterable[str]) -> None:
        """Inject cross-module reachability facts (phase-1 index).

        ``names`` are bare local def names proven jit-reachable through
        the project call graph (e.g. a helper here called from a jitted
        step in another module); R001/R003 pick them up exactly like
        locally-discovered reachability.
        """
        self.jit_reachable |= {n for n in names if n in self.functions}

    # -- helpers for rules ---------------------------------------------

    def enclosing_function(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def inside_loop(self, node: ast.AST, stop_at=None) -> bool:
        cur = self.parents.get(node)
        while cur is not None and cur is not stop_at:
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                return True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
            cur = self.parents.get(cur)
        return False

    def finding(self, rule_id: str, node: ast.AST, message: str) -> Finding:
        return Finding(rule_id, self.path, node.lineno, node.col_offset, message)


def _noqa_rules_on(ctx: ModuleContext, lineno: int) -> Optional[Set[str]]:
    """Rule ids a noqa comment on ``lineno`` names (empty set = all)."""
    line = ctx.lines[lineno - 1] if 0 < lineno <= len(ctx.lines) else ""
    m = NOQA_RE.search(line)
    if m is None:
        return None
    rules = m.group("rules")
    if rules is None:
        return set()
    return {r.strip() for r in rules.split(",") if r.strip()}


def _statement_spans(ctx: ModuleContext) -> List[tuple]:
    """(first_line, last_line) spans a first-line noqa covers.

    A simple statement (a multi-line call, assignment, return, ...)
    covers its full ``lineno..end_lineno`` span. A compound statement
    (if/for/while/with/def/try) covers only its HEADER — up to the line
    before its first body statement — so a noqa on ``if (...):`` cannot
    blanket-suppress the whole block under it.
    """
    spans = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.stmt):
            continue
        end = getattr(node, "end_lineno", None) or node.lineno
        body = getattr(node, "body", None)
        if isinstance(body, list) and body and isinstance(body[0], ast.stmt):
            end = max(node.lineno, body[0].lineno - 1)
        if end > node.lineno:
            spans.append((node.lineno, end))
    return spans


def _apply_suppressions(ctx: ModuleContext, findings: List[Finding]) -> List[Finding]:
    # suppression spans: the finding's own line always; a noqa on the
    # first line of a multi-line statement covers every line of that
    # statement (findings anchor to inner expression nodes, which can
    # start lines below the comment)
    span_rules: Dict[int, Set[str]] = {}  # finding line -> noqa'd rules
    for start, end in _statement_spans(ctx):
        rules = _noqa_rules_on(ctx, start)
        if rules is None:
            continue
        for line in range(start, end + 1):
            got = span_rules.get(line)
            if got is None:
                span_rules[line] = set(rules)
            elif rules and got:
                got |= rules
            else:
                span_rules[line] = set()  # bare noqa wins: all rules
    out = []
    for f in findings:
        suppressed = False
        for rules in (_noqa_rules_on(ctx, f.line), span_rules.get(f.line)):
            if rules is not None and (not rules or f.rule in rules):
                suppressed = True
        if suppressed:
            f = dataclasses.replace(f, suppressed=True)
        out.append(f)
    return out


def _run_rules(ctx: ModuleContext,
               select: Optional[Sequence[str]] = None) -> List[Finding]:
    """Phase 2 for one module: run the (selected) rule pack."""
    rules = all_rules()
    wanted = list(rules) if select is None else [r for r in rules if r in set(select)]
    findings: List[Finding] = []
    for rule_id in wanted:
        findings.extend(rules[rule_id].check(ctx))
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return _apply_suppressions(ctx, findings)


def _parse_context(src: str, path: str):
    """(ModuleContext, None) or (None, [E999 finding])."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return None, [Finding("E999", path, e.lineno or 1,
                              (e.offset or 1) - 1, f"syntax error: {e.msg}")]
    return ModuleContext(path, src, tree), None


def analyze_source(
    src: str, path: str = "<string>", select: Optional[Sequence[str]] = None
) -> List[Finding]:
    """Run the (selected) rule pack over one source string.

    Single-module entry point: the whole-program index degenerates to a
    one-module project (no cross-module edges, but rules that consult
    ``ctx.project`` still see a consistent view).
    """
    from .project import Project

    ctx, errors = _parse_context(src, path)
    if ctx is None:
        return errors
    Project([ctx])
    return _run_rules(ctx, select)


def analyze_file(path: str, select: Optional[Sequence[str]] = None) -> List[Finding]:
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    return analyze_source(src, path, select)


def iter_python_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)
        elif p.endswith(".py"):
            yield p


class AnalysisCache:
    """On-disk findings cache for the phase-2 check.

    One JSON file; per analyzed file an entry keyed by the file's
    ``(mtime, size)`` plus a digest of everything else its findings
    depend on: the engine version, the rule selection, and the
    cross-module facts the phase-1 index injected (reachability, axis
    universe). Phase 1 always re-parses — the index must be exact — so
    the cache only skips phase-2 rule execution, which is where the
    time goes. A dependency edit that changes a module's injected
    reachability changes the digest and re-checks the module even
    though its own mtime did not move.
    """

    def __init__(self, path: str):
        self.path = path
        self.dirty = False
        self.data: Dict[str, dict] = {}
        try:
            with open(path, "r", encoding="utf-8") as f:
                raw = json.load(f)
            if raw.get("version") == ANALYSIS_VERSION:
                self.data = raw.get("files", {})
        except (OSError, ValueError):
            pass

    @staticmethod
    def _stat_key(path: str):
        st = os.stat(path)
        return st.st_mtime, st.st_size

    def lookup(self, path: str, digest: str) -> Optional[List[Finding]]:
        ent = self.data.get(os.path.abspath(path))
        if ent is None or ent.get("digest") != digest:
            return None
        try:
            mtime, size = self._stat_key(path)
        except OSError:
            return None
        if ent.get("mtime") != mtime or ent.get("size") != size:
            return None
        return [Finding(**f) for f in ent.get("findings", [])]

    def store(self, path: str, digest: str, findings: List[Finding]) -> None:
        try:
            mtime, size = self._stat_key(path)
        except OSError:
            return
        self.data[os.path.abspath(path)] = {
            "mtime": mtime, "size": size, "digest": digest,
            "findings": [dataclasses.asdict(f) for f in findings],
        }
        self.dirty = True

    def save(self) -> None:
        if not self.dirty:
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"version": ANALYSIS_VERSION, "files": self.data}, f)
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _module_digest(project, ctx: ModuleContext,
                   select: Optional[Sequence[str]]) -> str:
    parts = [ANALYSIS_VERSION,
             ",".join(sorted(select)) if select else "*"]
    parts += project.reach_digest_parts(ctx)
    return hashlib.sha1("\x00".join(parts).encode()).hexdigest()


def analyze_paths(
    paths: Sequence[str], select: Optional[Sequence[str]] = None,
    cache_path: Optional[str] = None,
) -> List[Finding]:
    """Two-phase whole-program run over files/trees.

    Phase 1 parses every file and builds the cross-module index
    (``project.Project``); phase 2 runs the rule pack per module,
    consulting the on-disk cache when ``cache_path`` is given.
    """
    from .project import Project

    findings: List[Finding] = []
    contexts: List[ModuleContext] = []
    for path in iter_python_files(paths):
        with open(path, "r", encoding="utf-8") as f:
            src = f.read()
        ctx, errors = _parse_context(src, path)
        if ctx is None:
            findings.extend(errors)
        else:
            contexts.append(ctx)
    project = Project(contexts)
    cache = AnalysisCache(cache_path) if cache_path else None
    for ctx in contexts:
        if cache is not None:
            digest = _module_digest(project, ctx, select)
            got = cache.lookup(ctx.path, digest)
            if got is None:
                got = _run_rules(ctx, select)
                cache.store(ctx.path, digest, got)
            findings.extend(got)
        else:
            findings.extend(_run_rules(ctx, select))
    if cache is not None:
        cache.save()
    return findings


def _github_escape(s: str) -> str:
    return s.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A")


def format_github(f: Finding, warn_only: bool = False) -> str:
    """One GitHub Actions workflow-command annotation per finding."""
    level = "notice" if f.suppressed else ("warning" if warn_only else "error")
    rule = all_rules().get(f.rule)
    title = f"{f.rule} {rule.name}" if rule else f.rule
    msg = f.message + (" (suppressed)" if f.suppressed else "")
    return (f"::{level} file={f.path},line={f.line},col={f.col + 1},"
            f"title={_github_escape(title)}::{_github_escape(msg)}")


def run_cli(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Whole-program JAX/Pallas static-analysis pass: "
        "transfer sanitizer, dtype/collective/padding/concurrency/kernel "
        "contract lint. Exits 1 on unsuppressed findings.",
    )
    ap.add_argument("paths", nargs="*", help="files or directories to analyze")
    ap.add_argument("--select", default=None, help="comma-separated rule ids (default: all)")
    ap.add_argument("--format", choices=("text", "json", "github"),
                    default="text",
                    help="github emits workflow-command annotations")
    ap.add_argument("--warn-only", action="store_true",
                    help="report findings but exit 0 (advisory lanes)")
    ap.add_argument("--cache", default=".repro-analysis.cache.json",
                    metavar="FILE",
                    help="on-disk findings cache (default: %(default)s)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the findings cache")
    ap.add_argument("--report", default=None, metavar="FILE",
                    help="also write the full JSON findings report to FILE")
    ap.add_argument("--list-rules", action="store_true", help="print the rule pack and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in all_rules().values():
            print(f"{rule.id}  {rule.name}\n    {rule.description}")
        return 0
    if not args.paths:
        ap.error("no paths given (or use --list-rules)")
    select = [s.strip() for s in args.select.split(",")] if args.select else None
    cache_path = None if args.no_cache else args.cache
    findings = analyze_paths(args.paths, select, cache_path=cache_path)
    live = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]
    if args.report:
        with open(args.report, "w", encoding="utf-8") as fh:
            json.dump([dataclasses.asdict(f) for f in findings], fh, indent=2)
    if args.format == "json":
        print(json.dumps([dataclasses.asdict(f) for f in findings], indent=2))
    else:
        for f in findings:
            print(format_github(f, args.warn_only) if args.format == "github"
                  else f.format())
        by_rule: Dict[str, int] = {}
        for f in live:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        stats = ", ".join(f"{k}={v}" for k, v in sorted(by_rule.items())) or "none"
        print(
            f"repro.analysis: {len(live)} finding(s) ({stats}), "
            f"{len(suppressed)} suppressed",
            file=sys.stderr,
        )
    return 0 if args.warn_only else (1 if live else 0)
