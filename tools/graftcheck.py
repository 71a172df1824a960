#!/usr/bin/env python
"""graftcheck: JAX-aware static analysis for the serving engine.

The engine's hardest invariants are runtime-invisible on the CPU-only
tier-1 container: a use-after-donate "works" on CPU and detonates on a
TPU (the donated buffer is really gone there), a lock-discipline slip
needs pod-scale concurrency to fire, and a tracer `bool()` only fails
once the offending branch actually traces.  This tool checks those
properties at the AST level — dependency-free (stdlib `ast` only, no
JAX import), whole-repo, in seconds — and rides tier-1 via
``tests/test_graftcheck.py``.

Four passes, stable rule ids:

==============  =====================================================
rule id         meaning
==============  =====================================================
use-after-donate  a local name / attribute passed in a DONATED
                  position of a jitted call is read again afterwards
                  without being rebound (the buffer no longer exists
                  on TPU; CPU aliases it and silently "works")
donation-vector   a function with a ``dstate`` parameter (the
                  engine's carry pytree) is jitted WITHOUT donating
                  that argument — carry programs must share one
                  donation story or the pipeline's in-place chain
                  breaks
donation-sharding a name that is DONATED in a function is also passed
                  to ``jax.device_put`` / ``with_sharding_constraint``
                  in that function — resharding a donated carry
                  between issue and reuse changes the buffer's
                  sharding out from under the donation chain (the
                  next call recompiles or silently copies instead of
                  aliasing); reshard at construction (the fresh
                  carry's jitted init), never mid-chain
host-sync         ``bool()/int()/float()``, ``.item()``, or a
                  ``np.*`` call on a traced value inside a
                  jit-reachable function (an implicit device sync,
                  or a trace error)
tracer-control-flow  Python ``if``/``while``/``assert`` on a traced
                  value inside a jit-reachable function
traced-time       ``time.time()``/``perf_counter()`` etc. inside a
                  jit-reachable function (traces to a constant)
unguarded-write   a write to a ``# guarded_by:`` annotated attribute
                  outside ``with <lock>:`` / outside a method
                  annotated for the owning thread domain
unguarded-read    same, for reads — only for annotations WITHOUT the
                  ``[writes]`` qualifier (writes-only mode is for
                  fields with a documented torn-read contract)
bad-annotation    a ``guarded_by``/``runs-on``/``holds`` annotation
                  that doesn't parse or doesn't attach to anything
metric-drift      metric families disagree between the code
                  collectors, the docs/observability.md catalog, and
                  tools/obs_check.py's enforced list
env-drift         an ``MLCOMP_*`` env var read (or set for a child
                  process) in code but missing from docs/serving.md's
                  environment table — or documented but unused
fault-drift       a fault point injected via utils/faults.py that no
                  chaos scenario or test ever arms (dead chaos
                  surface), or armed but never injected (stale test)
flag-drift        a ``--flag`` referenced in README/docs that no
                  ``add_argument`` in the repo defines
bad-suppression   a ``graftcheck: ignore`` comment without a reason
==============  =====================================================

Annotations (the lock-discipline vocabulary)::

    self._profile = None   # guarded_by: _prof_lock [writes]
    self._dstate = ...     # guarded_by: loop
    def _drain(self):      # graftcheck: runs-on(worker)
    def _evict(self):      # graftcheck: holds(_lock)

``guarded_by`` names either a lock attribute of the same class
(detected as a ``threading.Lock()/RLock()/Condition()`` assignment) or
a thread DOMAIN (``loop``, ``worker`` — the single thread
entitled to the state; a watchdog-restart path that has proven the
loop dead may legitimately carry ``runs-on(loop)``).  ``[writes]``
enforces writes only — for fields with a documented torn-read
monitoring contract (the engine's ``_stats`` idiom).  Accesses in the
declaring class's ``__init__`` are always allowed (construction is
single-threaded).

Suppressions::

    self._stats["requests"] += 1  # graftcheck: ignore[unguarded-write] -- GIL-atomic; sole off-loop writer

The reason after ``--`` is mandatory; a bare ignore is itself a
finding.  A suppression on its own line applies to the next line.

CLI::

    python -m tools.graftcheck              # human output, exit 1 on findings
    python -m tools.graftcheck --json       # machine output
    python -m tools.graftcheck --rules use-after-donate,host-sync
    python -m tools.graftcheck --list-env   # dump the env/metric/fault
    python -m tools.graftcheck --list-metrics   # inventories the drift
    python -m tools.graftcheck --list-faults    # pass extracted from code

Scope and honesty: the donation and trace passes are heuristic — they
resolve what is statically resolvable (literal functions passed to
``jit``/``lax.scan``/``vmap``, the engine's ``self._fns`` getter
idiom) and say nothing about the rest.  docs/static_analysis.md
documents the exact approximations.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_RULES = (
    "use-after-donate", "donation-vector", "donation-sharding",
    "host-sync", "tracer-control-flow", "traced-time",
    "unguarded-write", "unguarded-read", "bad-annotation",
    "metric-drift", "env-drift", "fault-drift", "flag-drift",
    "bad-suppression",
)

# the seven files whose shared-state ownership story is annotated
LOCK_FILES = (
    "mlcomp_tpu/engine.py",
    "mlcomp_tpu/serve.py",
    "mlcomp_tpu/kvpool/pool.py",
    "mlcomp_tpu/kvpool/allocator.py",
    "mlcomp_tpu/cache/prefix_index.py",
    "mlcomp_tpu/cache/kv_store.py",
    "mlcomp_tpu/obs/metrics.py",
)

# metric families docs/observability.md documents as CONDITIONAL on a
# service configuration the tier-1 obs_check daemon does not run —
# they are exempt from the "docs ⊆ obs_check enforced list" direction
# (and only from that direction).  Keep each entry justified.  What a
# model's layers count (``mlcomp_engine_<group>_<entry>_total``: the
# tier-1 obs_check daemon serves a dense transformer_lm, whose engine
# emits none of them) is not listed here: ``collect_count_metrics``
# reads those names from the layers' own tables.
CONDITIONAL_METRICS = {
    # sharded engines only (the tier-1 obs_check daemon is mesh-less)
    "mlcomp_engine_mesh_devices",
    "mlcomp_engine_is_coordinator",
    # prefill replicas only (--phase prefill; the tier-1 obs_check
    # daemon is a paged decode-capable daemon — the EXPORT side's
    # counters are asserted by its dedicated disaggregation leg
    # against a prefill service's own scrape, not the enforced list)
    "mlcomp_engine_handoffs_exported_total",
    "mlcomp_engine_kv_pages_exported_total",
    "mlcomp_engine_handoff_bytes_exported_total",
}

MUTATOR_METHODS = {
    "append", "appendleft", "extend", "insert", "pop", "popleft",
    "popitem", "remove", "clear", "update", "setdefault", "add",
    "discard", "sort", "reverse",
}

# attribute accesses that yield STATIC metadata, not a traced value
TAINT_BREAKERS = {"shape", "ndim", "dtype", "size", "itemsize"}

JNP_CALL_RE = re.compile(
    r"^(jnp|jax\.numpy|jax\.nn|jax\.lax|jax\.random|lax)\."
)

GUARD_RE = re.compile(
    r"#\s*guarded_by:\s*([A-Za-z_]\w*)\s*(\[writes\])?"
)
RUNS_RE = re.compile(r"#\s*graftcheck:\s*runs-on\((\w+)\)")
HOLDS_RE = re.compile(r"#\s*graftcheck:\s*holds\((\w+)\)")
IGNORE_RE = re.compile(
    r"#\s*graftcheck:\s*ignore\[([\w\-, ]+)\](\s*--\s*(\S.*))?"
)


class Finding:
    __slots__ = ("rule", "path", "line", "message")

    def __init__(self, rule: str, path: str, line: int, message: str):
        self.rule = rule
        self.path = path
        self.line = int(line)
        self.message = message

    def key(self):
        return (self.path, self.line, self.rule, self.message)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"

    def as_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "path": self.path,
                "line": self.line, "message": self.message}


def dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` -> "a.b.c" for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ModuleInfo:
    """One parsed file: tree, lines, parent links, suppressions."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # line -> set of suppressed rules ({"*"} = all)
        self.suppress: Dict[int, Set[str]] = {}
        self.bad_suppressions: List[int] = []
        self._fn_ann_cache: Dict[int, Tuple[Set[str], Set[str]]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = IGNORE_RE.search(line)
            if not m:
                continue
            if not m.group(3):
                self.bad_suppressions.append(i)
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            target = i
            if line.strip().startswith("#"):
                target = i + 1  # standalone comment covers the next line
            self.suppress.setdefault(target, set()).update(rules)
        if self.suppress:
            # a finding may anchor to ANY line of a multi-line
            # statement (the offending node's lineno), while the
            # suppression comment sits on the statement's last physical
            # line — widen each suppression to its whole statement
            for node in ast.walk(self.tree):
                if not isinstance(node, ast.stmt) or hasattr(node, "body"):
                    continue  # simple statements only: a compound
                    # stmt's span covers its whole body
                end = getattr(node, "end_lineno", None) or node.lineno
                if end == node.lineno:
                    continue
                for line_no in list(self.suppress):
                    if node.lineno <= line_no <= end:
                        rules = self.suppress[line_no]
                        for ln in range(node.lineno, end + 1):
                            self.suppress.setdefault(ln, set()).update(
                                rules
                            )

    def enclosing_functions(self, node: ast.AST) -> List[ast.AST]:
        out = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(cur)
            cur = self.parents.get(cur)
        return out

    def enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = self.parents.get(cur)
        return None

    def def_region_lines(self, fn: ast.AST) -> Iterable[str]:
        """The ``def`` line(s) up to (and including) the first body
        statement's line — where runs-on/holds annotations live."""
        first = fn.body[0].lineno if fn.body else fn.lineno
        lo = fn.lineno
        return self.lines[lo - 1:first]


def load_modules(root: str, rels: Sequence[str]) -> Dict[str, ModuleInfo]:
    out: Dict[str, ModuleInfo] = {}
    for rel in rels:
        path = os.path.join(root, rel)
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            out[rel] = ModuleInfo(path, rel, src)
        except (OSError, SyntaxError):
            continue
    return out


def python_files(root: str, subdirs: Sequence[str]) -> List[str]:
    rels: List[str] = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rels.append(os.path.relpath(
                        os.path.join(dirpath, fn), root
                    ))
    return rels


# --------------------------------------------------------------- donation


def _donate_vector(call: ast.Call) -> Optional[Tuple[int, ...]]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                        e.value, int
                    ):
                        out.append(e.value)
                    else:
                        return None
                return tuple(out)
            return None
    return None


def _is_jit_call(call: ast.Call) -> bool:
    name = dotted(call.func)
    return bool(name) and (name == "jit" or name.endswith(".jit"))


def _local_defs(mi: ModuleInfo) -> Dict[ast.AST, Dict[str, ast.AST]]:
    """scope node -> {name: FunctionDef} for every def in the module
    (module, class, and function scopes)."""
    table: Dict[ast.AST, Dict[str, ast.AST]] = {}
    for node in ast.walk(mi.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scope = mi.parents.get(node)
            while scope is not None and not isinstance(
                scope, (ast.Module, ast.ClassDef, ast.FunctionDef,
                        ast.AsyncFunctionDef)
            ):
                scope = mi.parents.get(scope)
            table.setdefault(scope, {})[node.name] = node
    return table


def _resolve_name(mi: ModuleInfo, at: ast.AST, name: str,
                  defs: Dict[ast.AST, Dict[str, ast.AST]]):
    """Resolve ``name`` to a FunctionDef visible from ``at``."""
    scopes: List[ast.AST] = []
    cur: Optional[ast.AST] = at
    while cur is not None:
        if isinstance(cur, (ast.Module, ast.ClassDef, ast.FunctionDef,
                            ast.AsyncFunctionDef)):
            scopes.append(cur)
        cur = mi.parents.get(cur)
    for scope in scopes:
        hit = defs.get(scope, {}).get(name)
        if hit is not None:
            return hit
    return None


_COMPOUND_HEADERS = {
    ast.For: ("target", "iter"),
    ast.While: ("test",),
    ast.If: ("test",),
    ast.With: ("items",),
    ast.Try: (),
}


def _own_nodes(stmt: ast.stmt) -> List[ast.AST]:
    """The nodes that EXECUTE as part of this statement itself: for
    compound statements only the header expressions (their bodies are
    separate statements in the linear scan); nested function/class
    defs and lambdas are skipped (they run at call time)."""
    headers = _COMPOUND_HEADERS.get(type(stmt))
    roots: List[ast.AST]
    if headers is not None:
        roots = []
        for field in headers:
            v = getattr(stmt, field)
            roots.extend(v if isinstance(v, list) else [v])
    else:
        roots = [stmt]
    out: List[ast.AST] = []
    stack = list(roots)
    while stack:
        n = stack.pop()
        out.append(n)
        for c in ast.iter_child_nodes(n):
            if isinstance(c, (ast.Lambda, ast.FunctionDef,
                              ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            stack.append(c)
    return out


def _assign_targets_texts(stmt: ast.stmt) -> Set[str]:
    """Dotted texts this statement REBINDS (incl. tuple unpacking)."""
    out: Set[str] = set()

    def collect(t: ast.AST) -> None:
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect(e)
        elif isinstance(t, ast.Starred):
            collect(t.value)
        else:
            txt = dotted(t)
            if txt:
                out.add(txt)

    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            collect(t)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        collect(stmt.target)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        collect(stmt.target)  # loop targets rebind too
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            if item.optional_vars is not None:
                collect(item.optional_vars)
    # walrus targets anywhere in the statement's own expressions
    for node in _own_nodes(stmt):
        if isinstance(node, ast.NamedExpr):
            collect(node.target)
    return out


class _DonationGetters(ast.NodeVisitor):
    """Engine idiom: a method whose body jits-with-donation into
    ``self._fns[...]`` is a donating GETTER — ``self.method(...)(...)``
    call sites inherit its donation vector."""

    def __init__(self):
        self.getters: Dict[str, Tuple[int, ...]] = {}

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and _is_jit_call(sub):
                vec = _donate_vector(sub)
                if vec:
                    self.getters[node.name] = vec
                    break
        self.generic_visit(node)


def check_donation(mi: ModuleInfo) -> List[Finding]:
    findings: List[Finding] = []
    defs = _local_defs(mi)

    # 1) carry-consistency: a literal function with a `dstate` param
    #    jitted without donating that position
    for node in ast.walk(mi.tree):
        if not (isinstance(node, ast.Call) and _is_jit_call(node)):
            continue
        if not node.args:
            continue
        target = node.args[0]
        fn = None
        if isinstance(target, ast.Name):
            fn = _resolve_name(mi, node, target.id, defs)
        if fn is None:
            continue
        params = [a.arg for a in fn.args.args]
        vec = _donate_vector(node) or ()
        if "dstate" in params:
            idx = params.index("dstate")
            if idx not in vec:
                findings.append(Finding(
                    "donation-vector", mi.rel, node.lineno,
                    f"'{fn.name}' consumes the engine carry (param "
                    f"'dstate' at position {idx}) but the jit donates "
                    f"{vec or 'nothing'} — carry programs must donate "
                    "the carry or the in-place dispatch chain breaks",
                ))

    # 2) collect donating callables reachable from call sites
    getters = _DonationGetters()
    getters.visit(mi.tree)
    # function-scope -> {name: vector} for `var = jax.jit(f, donate…)`
    jit_vars: Dict[Optional[int], Dict[str, Tuple[int, ...]]] = {}
    for node in ast.walk(mi.tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and _is_jit_call(node.value)):
            vec = _donate_vector(node.value)
            if vec:
                fns = mi.enclosing_functions(node)
                key = id(fns[0]) if fns else None
                jit_vars.setdefault(key, {})[node.targets[0].id] = vec

    def call_vector(call: ast.Call,
                    scope_ids: List[Optional[int]]
                    ) -> Optional[Tuple[int, ...]]:
        # `var(...)` where var = jax.jit(f, donate_argnums=...)
        if isinstance(call.func, ast.Name):
            for key in scope_ids:
                vec = jit_vars.get(key, {}).get(call.func.id)
                if vec:
                    return vec
            return None
        # `self._insert_fn()(...)` / `self._fused_dispatch_fn(c)(...)`
        if isinstance(call.func, ast.Call):
            inner = call.func.func
            if isinstance(inner, ast.Attribute):
                return getters.getters.get(inner.attr)
        return None

    # 3) use-after-donate: linear scan of each function body
    for fn in ast.walk(mi.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        scope_ids: List[Optional[int]] = [id(fn)] + [
            id(f) for f in mi.enclosing_functions(fn)
        ] + [None]
        stmts: List[ast.stmt] = []

        def flatten(body: List[ast.stmt]) -> None:
            for s in body:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue  # runs at call time, not here
                stmts.append(s)
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(s, field, None)
                    if sub:
                        flatten(sub)
                for h in getattr(s, "handlers", []) or []:
                    flatten(h.body)

        flatten(fn.body)
        stmts.sort(key=lambda s: s.lineno)
        tainted: Dict[str, int] = {}  # expr text -> donating call line
        donated_any: Dict[str, int] = {}  # name -> first donation line
        reshards: List[Tuple[str, int, str]] = []  # (name, line, fn)
        for stmt in stmts:
            nodes = _own_nodes(stmt)
            rebound = _assign_targets_texts(stmt)
            # reads of donated-dead values in this statement
            if tainted:
                for node in nodes:
                    if isinstance(node, (ast.Name, ast.Attribute)) and (
                        isinstance(getattr(node, "ctx", None), ast.Load)
                    ):
                        txt = dotted(node)
                        if txt in tainted:
                            findings.append(Finding(
                                "use-after-donate", mi.rel, node.lineno,
                                f"'{txt}' was donated to the jitted "
                                f"call at line {tainted[txt]} and is "
                                "read again here — the buffer no "
                                "longer exists on TPU (CPU aliases it "
                                "and silently 'works')",
                            ))
                            del tainted[txt]
            for txt in rebound:
                tainted.pop(txt, None)
            # new donations from this statement
            for node in nodes:
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func) or ""
                leaf = name.split(".")[-1]
                if leaf in ("device_put", "with_sharding_constraint"
                            ) and node.args:
                    txt = dotted(node.args[0])
                    if txt is not None:
                        reshards.append((txt, node.lineno, leaf))
                vec = call_vector(node, scope_ids)
                if not vec:
                    continue
                for idx in vec:
                    if idx >= len(node.args):
                        continue
                    txt = dotted(node.args[idx])
                    if txt is None:
                        continue
                    donated_any.setdefault(txt, node.lineno)
                    if txt in rebound:
                        continue  # the same stmt rebinds it (the idiom)
                    tainted[txt] = node.lineno
        # donation-sharding: the same function both DONATES a name and
        # reshards it (device_put / with_sharding_constraint) — the
        # donated chain's buffer sharding changes between issue and
        # reuse, so the next donating call recompiles or copies
        # instead of aliasing.  Deliberately order-insensitive: loop
        # bodies donate and reuse across iterations, so a reshard
        # "before" the donation in source order still hits the chain
        # (a genuine construct-then-donate sequence in one function is
        # rare — suppress with a reason).
        for txt, line, how in reshards:
            if txt in donated_any:
                findings.append(Finding(
                    "donation-sharding", mi.rel, line,
                    f"'{txt}' is donated in this function (line "
                    f"{donated_any[txt]}) and resharded here by "
                    f"{how} — donation vectors must preserve "
                    "shardings: reshard at construction (the fresh "
                    "carry's jitted init with out_shardings), never "
                    "between issue and reuse",
                ))
    return findings


# ------------------------------------------------------------ trace pass

TIME_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "datetime.datetime.now", "datetime.now",
}

TRACED_SEED_SUFFIXES = (".jit", "lax.scan", ".vmap", "lax.cond",
                        "lax.while_loop", "lax.fori_loop")


def _seed_traced(mi: ModuleInfo, defs) -> List[ast.AST]:
    """Function nodes syntactically passed to jit / scan / vmap /
    cond / while_loop / fori_loop (Name or Lambda args)."""
    roots: List[ast.AST] = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if not name:
            continue
        if not (name == "jit" or name == "vmap" or name == "scan"
                or any(name.endswith(s) for s in TRACED_SEED_SUFFIXES)):
            continue
        for arg in node.args:
            if isinstance(arg, ast.Lambda):
                roots.append(arg)
            elif isinstance(arg, ast.Name):
                fn = _resolve_name(mi, node, arg.id, defs)
                if fn is not None:
                    roots.append(fn)
    return roots


def _expand_traced(mi: ModuleInfo, roots: List[ast.AST], defs
                   ) -> List[ast.AST]:
    """Follow same-module calls (plain names and self-methods) from
    the seeds, depth-bounded."""
    seen: Set[int] = set()
    out: List[ast.AST] = []
    frontier = [(r, 0) for r in roots]
    while frontier:
        fn, depth = frontier.pop()
        if id(fn) in seen or depth > 3:
            continue
        seen.add(id(fn))
        out.append(fn)
        cls = mi.enclosing_class(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Name):
                callee = _resolve_name(mi, fn, node.func.id, defs)
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id == "self" and cls is not None):
                callee = defs.get(cls, {}).get(node.func.attr)
            if callee is not None:
                frontier.append((callee, depth + 1))
    return out


def _expr_tainted(node: ast.AST, tainted: Set[str]) -> bool:
    """Does the expression reference a traced value?  Attribute access
    of static metadata (``.shape`` etc.) and ``len()`` break taint;
    results of arbitrary (non-jnp) calls are NOT considered traced."""
    if isinstance(node, ast.Attribute):
        if node.attr in TAINT_BREAKERS:
            return False
        return _expr_tainted(node.value, tainted)
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Subscript):
        return _expr_tainted(node.value, tainted)
    if isinstance(node, ast.Call):
        name = dotted(node.func)
        if name and JNP_CALL_RE.match(name):
            return True
        if name == "len":
            return False
        return False  # opaque call: assume host value (documented)
    if isinstance(node, (ast.BoolOp,)):
        return any(_expr_tainted(v, tainted) for v in node.values)
    if isinstance(node, ast.BinOp):
        return _expr_tainted(node.left, tainted) or _expr_tainted(
            node.right, tainted
        )
    if isinstance(node, ast.UnaryOp):
        return _expr_tainted(node.operand, tainted)
    if isinstance(node, ast.Compare):
        return _expr_tainted(node.left, tainted) or any(
            _expr_tainted(c, tainted) for c in node.comparators
        )
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_expr_tainted(e, tainted) for e in node.elts)
    if isinstance(node, ast.IfExp):
        return any(_expr_tainted(n, tainted)
                   for n in (node.test, node.body, node.orelse))
    return False


def check_traced_fn(mi: ModuleInfo, fn: ast.AST) -> List[Finding]:
    findings: List[Finding] = []
    if isinstance(fn, ast.Lambda):
        body_nodes = list(ast.walk(fn.body))
    else:
        body_nodes = [n for s in fn.body for n in ast.walk(s)]
    # Taint = values provably traced: results of jnp/jax.lax/jax.nn/
    # jax.random calls (+ arithmetic over them).  Parameters are NOT
    # tainted: the repo's traced functions routinely take static
    # Python knobs (top_k, causal, chunk widths) as plain params, and
    # flagging every `if knob:` would bury the real hazards.  The
    # price (documented in docs/static_analysis.md): a hazard on a
    # parameter used directly is missed unless it first flows through
    # a jnp op.
    tainted: Set[str] = set()
    # one forward sweep: direct assignments from jnp/jax calls or
    # tainted expressions taint their targets
    for node in body_nodes:
        if isinstance(node, ast.Assign) and _expr_tainted(
            node.value, tainted
        ):
            for txt in _assign_targets_texts(node):
                if "." not in txt:
                    tainted.add(txt)
    for node in body_nodes:
        # nested defs are analyzed on their own (reachability)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(node, (ast.If, ast.While)):
            if _expr_tainted(node.test, tainted):
                findings.append(Finding(
                    "tracer-control-flow", mi.rel, node.lineno,
                    "Python control flow on a traced value inside a "
                    "jit-reachable function — use lax.cond/select "
                    "(this either fails to trace or bakes in one "
                    "branch)",
                ))
        elif isinstance(node, ast.Assert):
            if _expr_tainted(node.test, tainted):
                findings.append(Finding(
                    "tracer-control-flow", mi.rel, node.lineno,
                    "assert on a traced value inside a jit-reachable "
                    "function (TracerBoolConversionError at trace "
                    "time)",
                ))
        elif isinstance(node, ast.Call):
            name = dotted(node.func)
            if name in TIME_CALLS:
                findings.append(Finding(
                    "traced-time", mi.rel, node.lineno,
                    f"{name}() inside a jit-reachable function traces "
                    "to a constant — hoist it to the host boundary",
                ))
            elif name in ("bool", "int", "float") and node.args and not (
                isinstance(node.args[0], ast.Constant)
            ) and _expr_tainted(node.args[0], tainted):
                findings.append(Finding(
                    "host-sync", mi.rel, node.lineno,
                    f"{name}() on a traced value — an implicit host "
                    "sync (or TracerBoolConversionError); keep it on "
                    "device or fetch explicitly at the boundary",
                ))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item" and not node.args):
                findings.append(Finding(
                    "host-sync", mi.rel, node.lineno,
                    ".item() inside a jit-reachable function — an "
                    "implicit device sync (TracerError under jit)",
                ))
            elif name and (name.startswith("np.")
                           or name.startswith("numpy.")) and any(
                _expr_tainted(a, tainted) for a in node.args
            ):
                findings.append(Finding(
                    "host-sync", mi.rel, node.lineno,
                    f"{name}() on a traced value — numpy forces a "
                    "device sync / concrete value inside a trace; use "
                    "jnp or move it to the host boundary",
                ))
    return findings


def check_trace(mi: ModuleInfo) -> List[Finding]:
    defs = _local_defs(mi)
    roots = _seed_traced(mi, defs)
    findings: List[Finding] = []
    for fn in _expand_traced(mi, roots, defs):
        findings.extend(check_traced_fn(mi, fn))
    return findings


# ------------------------------------------------------------- lock pass


class _GuardInfo:
    __slots__ = ("cls", "attr", "guard", "writes_only", "line")

    def __init__(self, cls, attr, guard, writes_only, line):
        self.cls = cls
        self.attr = attr
        self.guard = guard
        self.writes_only = writes_only
        self.line = line


def _collect_lock_attrs(mi: ModuleInfo, cls: ast.ClassDef) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Call
        ):
            name = dotted(node.value.func) or ""
            if name.split(".")[-1] in ("Lock", "RLock", "Condition"):
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        out.add(t.attr)
    return out


def _fn_annotations(mi: ModuleInfo, fn: ast.AST) -> Tuple[Set[str],
                                                          Set[str]]:
    cached = mi._fn_ann_cache.get(id(fn))
    if cached is not None:
        return cached
    runs: Set[str] = set()
    holds: Set[str] = set()
    for line in mi.def_region_lines(fn):
        for m in RUNS_RE.finditer(line):
            runs.add(m.group(1))
        for m in HOLDS_RE.finditer(line):
            holds.add(m.group(1))
    mi._fn_ann_cache[id(fn)] = (runs, holds)
    return runs, holds


def _is_write_access(mi: ModuleInfo, node: ast.Attribute) -> bool:
    if isinstance(node.ctx, (ast.Store, ast.Del)):
        return True
    parent = mi.parents.get(node)
    # self.x[...] = / self.x[...] += : the Subscript carries Store
    if isinstance(parent, ast.Subscript) and parent.value is node and (
        isinstance(parent.ctx, (ast.Store, ast.Del))
    ):
        return True
    # slice-assign targets: self.x[:] = ...
    if isinstance(parent, ast.Subscript) and parent.value is node:
        gp = mi.parents.get(parent)
        if isinstance(gp, ast.AugAssign) and gp.target is parent:
            return True
    # mutator method call: self.x.append(...)
    if (isinstance(parent, ast.Attribute)
            and parent.value is node
            and parent.attr in MUTATOR_METHODS):
        gp = mi.parents.get(parent)
        if isinstance(gp, ast.Call) and gp.func is parent:
            return True
    # aug-assign directly on the attribute: self.x += 1
    if isinstance(parent, ast.AugAssign) and parent.target is node:
        return True
    return False


def _under_lock(mi: ModuleInfo, node: ast.AST, recv: str,
                guard: str) -> bool:
    cur = mi.parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.With):
            for item in cur.items:
                txt = dotted(item.context_expr)
                # receiver-matched ONLY: `with self._lock:` guards
                # self.X, `with index._lock:` guards index.X.  A bare
                # `with _lock:` (or an alias) is NOT accepted — a
                # same-named but different lock must not certify the
                # access; write the explicit form.
                if txt == f"{recv}.{guard}":
                    return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _, holds = _fn_annotations(mi, cur)
            if guard in holds:
                return True
        cur = mi.parents.get(cur)
    return False


def check_locks(mods: Dict[str, ModuleInfo]) -> List[Finding]:
    findings: List[Finding] = []
    guards: List[_GuardInfo] = []
    lock_attrs: Dict[Tuple[str, str], Set[str]] = {}

    # collect annotations
    for rel, mi in mods.items():
        for cls in [n for n in ast.walk(mi.tree)
                    if isinstance(n, ast.ClassDef)]:
            lock_attrs[(rel, cls.name)] = _collect_lock_attrs(mi, cls)
        for i, line in enumerate(mi.lines, start=1):
            m = GUARD_RE.search(line)
            if not m:
                continue
            guard, writes_only = m.group(1), bool(m.group(2))
            # attach to a `self.X = ...` on this line
            attached = False
            for node in ast.walk(mi.tree):
                if isinstance(node, (ast.Assign, ast.AnnAssign)) and (
                    node.lineno <= i <= (node.end_lineno or node.lineno)
                ):
                    targets = (
                        node.targets if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for t in targets:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                            cls = mi.enclosing_class(node)
                            if cls is None:
                                continue
                            guards.append(_GuardInfo(
                                (rel, cls.name), t.attr, guard,
                                writes_only, i,
                            ))
                            attached = True
            if not attached:
                findings.append(Finding(
                    "bad-annotation", rel, i,
                    "guarded_by annotation does not attach to a "
                    "`self.<attr> = ...` assignment on this line",
                ))

    by_class: Dict[Tuple[str, str], Dict[str, _GuardInfo]] = {}
    by_attr: Dict[str, List[_GuardInfo]] = {}
    for g in guards:
        by_class.setdefault(g.cls, {})[g.attr] = g
        by_attr.setdefault(g.attr, []).append(g)

    # enforce
    for rel, mi in mods.items():
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Attribute):
                continue
            recv = dotted(node.value)
            if recv is None:
                continue
            g: Optional[_GuardInfo] = None
            encl_cls = mi.enclosing_class(node)
            if recv == "self" and encl_cls is not None:
                g = by_class.get((rel, encl_cls.name), {}).get(node.attr)
            if g is None:
                cands = by_attr.get(node.attr, [])
                if recv != "self" and len(cands) == 1:
                    g = cands[0]
                elif recv != "self" and len({c.guard for c in cands}) > 1:
                    continue  # ambiguous foreign access: skip
                elif recv != "self" and len(cands) > 1:
                    g = cands[0]
            if g is None:
                continue
            is_write = _is_write_access(mi, node)
            if g.writes_only and not is_write:
                continue
            fns = mi.enclosing_functions(node)
            if fns and fns[-1].name == "__init__" and recv == "self" and (
                encl_cls is not None and (rel, encl_cls.name) == g.cls
            ):
                continue  # construction is single-threaded
            decl_rel, decl_cls = g.cls
            locks = lock_attrs.get(g.cls, set())
            ok = False
            if g.guard in locks or g.guard.endswith("lock"):
                ok = _under_lock(mi, node, recv, g.guard)
            else:  # thread-domain guard
                for fn in fns:
                    runs, _ = _fn_annotations(mi, fn)
                    if g.guard in runs:
                        ok = True
                        break
            if ok:
                continue
            rule = "unguarded-write" if is_write else "unguarded-read"
            kind = "write to" if is_write else "read of"
            where = (
                f"`with {g.guard}:`" if (g.guard in locks
                                         or g.guard.endswith("lock"))
                else f"a method annotated runs-on({g.guard})"
            )
            findings.append(Finding(
                rule, rel, node.lineno,
                f"{kind} '{recv}.{node.attr}' (guarded_by: {g.guard}"
                f"{' [writes]' if g.writes_only else ''}, declared "
                f"{decl_rel}:{g.line} in {decl_cls}) outside {where}",
            ))
    return findings


# ------------------------------------------------------------ drift pass


ENV_KEY_RE = re.compile(r"^MLCOMP_\w+$")


def collect_env_vars(mods: Dict[str, ModuleInfo]
                     ) -> Dict[str, List[Tuple[str, int, str]]]:
    """env name -> [(rel, line, 'read'|'set')] across the code set."""
    out: Dict[str, List[Tuple[str, int, str]]] = {}

    def record(name: str, rel: str, line: int, kind: str) -> None:
        if ENV_KEY_RE.match(name):
            out.setdefault(name, []).append((rel, line, kind))

    for rel, mi in mods.items():
        if rel == "tools/graftcheck.py":
            continue  # this tool's own rule strings are not env reads
        for node in ast.walk(mi.tree):
            # os.environ.get("X", ...) / os.getenv("X")
            if isinstance(node, ast.Call):
                if node.args and isinstance(
                    node.args[0], ast.Constant
                ) and isinstance(node.args[0].value, str) and (
                    ENV_KEY_RE.match(node.args[0].value)
                ):
                    record(node.args[0].value, rel, node.lineno, "read")
            # environ["X"] loads, env["X"] = ... stores
            if isinstance(node, ast.Subscript) and isinstance(
                node.slice, ast.Constant
            ) and isinstance(node.slice.value, str):
                base = dotted(node.value) or ""
                key = node.slice.value
                if isinstance(node.ctx, ast.Load) and base.endswith(
                    "environ"
                ):
                    record(key, rel, node.lineno, "read")
                elif isinstance(node.ctx, ast.Store):
                    record(key, rel, node.lineno, "set")
            # "X" in os.environ
            if isinstance(node, ast.Compare) and isinstance(
                node.left, ast.Constant
            ) and isinstance(node.left.value, str) and any(
                isinstance(op, (ast.In, ast.NotIn))
                for op in node.ops
            ):
                for comp in node.comparators:
                    if (dotted(comp) or "").endswith("environ"):
                        record(node.left.value, rel, node.lineno, "read")
    return out


def parse_md_section(md: str, heading: str) -> str:
    lines = md.splitlines()
    out: List[str] = []
    active = False
    for line in lines:
        if line.startswith("## "):
            active = line.strip() == heading
            continue
        if active:
            out.append(line)
    return "\n".join(out)


BACKTICK_RE = re.compile(r"`([^`]+)`")


def parse_env_table(serving_md: str) -> Set[str]:
    sec = parse_md_section(serving_md, "## Environment variables")
    out: Set[str] = set()
    for line in sec.splitlines():
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if not cells:
            continue
        m = BACKTICK_RE.search(cells[0])
        if m and ENV_KEY_RE.match(m.group(1)):
            out.add(m.group(1))
    return out


def parse_metric_docs(obs_md: str,
                      heading: str = "## Metrics catalog — serve daemon"
                      ) -> Set[str]:
    sec = parse_md_section(obs_md, heading)
    out: Set[str] = set()
    for line in sec.splitlines():
        if not line.startswith("|"):
            continue
        name_cell = line.strip("|").split("|")[0]
        for tok in BACKTICK_RE.findall(name_cell):
            tok = re.sub(r"\{[^}]*=[^}]*\}", "", tok)  # label suffix
            m = re.match(r"^([a-z0-9_]*)\{([a-z0-9_,]+)\}([a-z0-9_]*)$",
                         tok)
            if m:  # brace expansion: prefix{a,b,c}suffix
                for mid in m.group(2).split(","):
                    name = m.group(1) + mid + m.group(3)
                    if name.startswith("mlcomp_"):
                        out.add(name)
                continue
            if re.match(r"^mlcomp_[a-z0-9_]+$", tok):
                out.add(tok)
    return out


METRIC_FN_NAMES = {"counter", "gauge", "histogram", "ctr", "gau"}


def collect_code_metrics(mods: Dict[str, ModuleInfo]
                         ) -> Dict[str, Tuple[str, int]]:
    """metric name (or glob 'prefix*suffix' for f-strings) ->
    (rel, line), from first args of counter/gauge/histogram calls."""
    out: Dict[str, Tuple[str, int]] = {}
    for rel, mi in mods.items():
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fname = (dotted(node.func) or "").split(".")[-1]
            if fname not in METRIC_FN_NAMES:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(
                arg.value, str
            ) and arg.value.startswith("mlcomp_"):
                out.setdefault(arg.value, (rel, node.lineno))
            elif isinstance(arg, ast.JoinedStr):
                parts: List[str] = []
                for v in arg.values:
                    if isinstance(v, ast.Constant):
                        parts.append(v.value)
                    else:
                        parts.append("*")
                pat = "".join(parts)
                if pat.startswith("mlcomp_"):
                    out.setdefault(pat, (rel, node.lineno))
    return out


def collect_count_metrics(mods: Dict[str, ModuleInfo]) -> Set[str]:
    """The metric of every entry of every ``count_group(name, ((entry,
    help), ...))`` table a file under mlcomp_tpu/models/ declares (the
    name and the entries are literals there, for this reader)."""
    out: Set[str] = set()
    for rel, mi in mods.items():
        if not rel.startswith("mlcomp_tpu/models/"):
            continue
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call) or len(node.args) < 2:
                continue
            if (dotted(node.func) or "").split(".")[-1] != "count_group":
                continue
            group, entries = node.args[0], node.args[1]
            if not (isinstance(group, ast.Constant)
                    and isinstance(entries, (ast.Tuple, ast.List))):
                continue
            for entry in entries.elts:
                if isinstance(entry, (ast.Tuple, ast.List)) and entry.elts \
                        and isinstance(entry.elts[0], ast.Constant):
                    out.add(f"mlcomp_engine_{group.value}_"
                            f"{entry.elts[0].value}_total")
    return out


def _glob_match(pattern: str, name: str) -> bool:
    return re.fullmatch(
        ".*".join(re.escape(p) for p in pattern.split("*")), name
    ) is not None


def parse_obs_check_list(mi: ModuleInfo,
                         list_name: str = "DOCUMENTED_SERVE_METRICS"
                         ) -> Tuple[Set[str], int]:
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == list_name
            for t in node.targets
        ) and isinstance(node.value, (ast.List, ast.Tuple)):
            names = {
                e.value for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(
                    e.value, str
                )
            }
            return names, node.lineno
    return set(), 0


def collect_fault_points(mods: Dict[str, ModuleInfo]
                         ) -> Dict[str, Tuple[str, int]]:
    out: Dict[str, Tuple[str, int]] = {}
    for rel, mi in mods.items():
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Call):
                fname = (dotted(node.func) or "").split(".")[-1]
                if fname in ("inject", "_inject_fault") and node.args:
                    a = node.args[0]
                    if isinstance(a, ast.Constant) and isinstance(
                        a.value, str
                    ):
                        out.setdefault(a.value, (rel, node.lineno))
    return out


def collect_armed_points(mods: Dict[str, ModuleInfo]) -> Set[str]:
    out: Set[str] = set()
    for rel, mi in mods.items():
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Call):
                fname = (dotted(node.func) or "").split(".")[-1]
                if fname == "arm" and node.args:
                    a = node.args[0]
                    if isinstance(a, ast.Constant) and isinstance(
                        a.value, str
                    ):
                        out.add(a.value)
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ) and ":" in node.value:
                # MLCOMP_FAULTS-style spec strings ("point:kill:1")
                for item in node.value.split(","):
                    parts = item.split(":")
                    if len(parts) >= 2 and parts[1].startswith(
                        ("raise", "kill", "sleep")
                    ):
                        out.add(parts[0].strip())
    return out


FLAG_RE = re.compile(r"`[^`]*?(--[a-z][a-z0-9-]+)")


def collect_cli_flags(mods: Dict[str, ModuleInfo]) -> Set[str]:
    out: Set[str] = set()
    for rel, mi in mods.items():
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Call):
                fname = (dotted(node.func) or "").split(".")[-1]
                if fname == "add_argument":
                    for a in node.args:
                        if isinstance(a, ast.Constant) and isinstance(
                            a.value, str
                        ) and a.value.startswith("--"):
                            out.add(a.value)
    return out


def check_drift(root: str,
                mods: Optional[Dict[str, ModuleInfo]] = None
                ) -> List[Finding]:
    """``mods`` (rel -> ModuleInfo for mlcomp_tpu/ and tools/) lets
    run_passes share its parse; standalone calls re-parse."""
    findings: List[Finding] = []
    if mods is None:
        mods = load_modules(root, python_files(
            root, ("mlcomp_tpu", "tools")
        ))
    code = {
        rel: mi for rel, mi in mods.items()
        if not rel.startswith("tools/")
    }
    tools_mods = {
        rel: mi for rel, mi in mods.items() if rel.startswith("tools/")
    }
    tests_mods = load_modules(root, python_files(root, ("tests",)))

    def read(rel: str) -> str:
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as f:
                return f.read()
        except OSError:
            return ""

    serving_md = read("docs/serving.md")
    obs_md = read("docs/observability.md")

    # ---- env vars: code set vs the serving.md table
    env_code = collect_env_vars({**code, **tools_mods})
    env_docs = parse_env_table(serving_md)
    if "## Environment variables" not in serving_md:
        findings.append(Finding(
            "env-drift", "docs/serving.md", 1,
            "no '## Environment variables' table found — the env-var "
            "contract is undocumented",
        ))
    for name, sites in sorted(env_code.items()):
        if name not in env_docs:
            rel, line, kind = sites[0]
            findings.append(Finding(
                "env-drift", rel, line,
                f"env var {name} is {kind} here but missing from "
                "docs/serving.md's '## Environment variables' table",
            ))
    for name in sorted(env_docs - set(env_code)):
        findings.append(Finding(
            "env-drift", "docs/serving.md", 1,
            f"env var {name} is documented but never read or set in "
            "mlcomp_tpu/ or tools/ — stale row",
        ))

    # ---- metrics: collectors vs docs catalog vs obs_check list
    metric_mods = {
        rel: mi for rel, mi in code.items()
        if rel in ("mlcomp_tpu/engine.py", "mlcomp_tpu/serve.py")
        or rel.startswith("mlcomp_tpu/obs/")
    }
    code_metrics = collect_code_metrics(metric_mods)
    docs_metrics = parse_metric_docs(obs_md)
    obs_mi = tools_mods.get("tools/obs_check.py")
    enforced, enforced_line = (
        parse_obs_check_list(obs_mi) if obs_mi else (set(), 0)
    )
    internal = {"mlcomp_metrics_collector_errors_total"}
    for name, (rel, line) in sorted(code_metrics.items()):
        if name in internal:
            continue
        if "*" in name:
            if not any(_glob_match(name, d) for d in docs_metrics):
                findings.append(Finding(
                    "metric-drift", rel, line,
                    f"metric family pattern {name!r} registered here "
                    "matches nothing in docs/observability.md's serve-"
                    "daemon catalog",
                ))
        elif name not in docs_metrics:
            findings.append(Finding(
                "metric-drift", rel, line,
                f"metric {name} registered here is missing from "
                "docs/observability.md's serve-daemon catalog",
            ))
    patterns = [n for n in code_metrics if "*" in n]
    for name in sorted(docs_metrics):
        if name in code_metrics:
            continue
        if any(_glob_match(p, name) for p in patterns):
            continue
        findings.append(Finding(
            "metric-drift", "docs/observability.md", 1,
            f"documented serve-daemon metric {name} is registered by "
            "no collector in engine.py/serve.py/obs/ — stale row",
        ))
    for name in sorted(enforced - docs_metrics):
        findings.append(Finding(
            "metric-drift", "tools/obs_check.py", enforced_line,
            f"obs_check enforces {name} but docs/observability.md's "
            "serve-daemon catalog does not document it",
        ))
    conditional = CONDITIONAL_METRICS | collect_count_metrics(code)
    for name in sorted(docs_metrics - enforced - conditional):
        findings.append(Finding(
            "metric-drift", "tools/obs_check.py", enforced_line or 1,
            f"documented metric {name} is missing from obs_check's "
            "DOCUMENTED_SERVE_METRICS enforcement list (conditional "
            "families belong in graftcheck's CONDITIONAL_METRICS with "
            "a justification; a layer's counts in a count_group table "
            "under mlcomp_tpu/models/)",
        ))

    # ---- fleet control-plane metrics: the same three-way sync for
    # mlcomp_tpu/fleet/ collectors vs the fleet docs catalog vs
    # obs_check's DOCUMENTED_FLEET_METRICS list (the fleet surfaces
    # scrape from the ROUTER's /metrics, not the serve daemon's, so
    # they get their own catalog section and enforcement list)
    fleet_mods = {
        rel: mi for rel, mi in code.items()
        if rel.startswith("mlcomp_tpu/fleet/")
    }
    fleet_code = collect_code_metrics(fleet_mods)
    fleet_docs = parse_metric_docs(
        obs_md, heading="## Metrics catalog — fleet control plane"
    )
    fleet_enforced, fleet_line = (
        parse_obs_check_list(obs_mi, "DOCUMENTED_FLEET_METRICS")
        if obs_mi else (set(), 0)
    )
    for name, (rel, line) in sorted(fleet_code.items()):
        if name not in fleet_docs:
            findings.append(Finding(
                "metric-drift", rel, line,
                f"fleet metric {name} registered here is missing from "
                "docs/observability.md's fleet control-plane catalog",
            ))
    for name in sorted(fleet_docs - set(fleet_code)):
        findings.append(Finding(
            "metric-drift", "docs/observability.md", 1,
            f"documented fleet metric {name} is registered by no "
            "collector in mlcomp_tpu/fleet/ — stale row",
        ))
    for name in sorted(fleet_enforced - fleet_docs):
        findings.append(Finding(
            "metric-drift", "tools/obs_check.py", fleet_line,
            f"obs_check enforces fleet metric {name} but "
            "docs/observability.md's fleet catalog does not document "
            "it",
        ))
    for name in sorted(fleet_docs - fleet_enforced):
        findings.append(Finding(
            "metric-drift", "tools/obs_check.py", fleet_line or 1,
            f"documented fleet metric {name} is missing from "
            "obs_check's DOCUMENTED_FLEET_METRICS enforcement list",
        ))

    # ---- fault points vs the chaos/test surface that drives them
    points = collect_fault_points(code)
    armed = collect_armed_points({**tools_mods, **tests_mods})
    for point, (rel, line) in sorted(points.items()):
        if point not in armed:
            findings.append(Finding(
                "fault-drift", rel, line,
                f"fault point {point!r} is injected here but no chaos "
                "scenario (tools/chaoscheck.py) or test ever arms it "
                "— dead chaos surface",
            ))

    # ---- doc-referenced CLI flags must exist
    defined = collect_cli_flags({**code, **tools_mods})
    doc_files = ["README.md", "docs/serving.md", "docs/observability.md",
                 "docs/prefix_cache.md", "docs/static_analysis.md"]
    for rel in doc_files:
        text = read(rel)
        for i, line in enumerate(text.splitlines(), start=1):
            for m in FLAG_RE.finditer(line):
                flag = m.group(1)
                # docs spell some flags with their value glued on
                base = flag.split("=")[0]
                if base in defined:
                    continue
                if any(d.startswith(base) for d in defined):
                    continue
                findings.append(Finding(
                    "flag-drift", rel, i,
                    f"doc references CLI flag {base!r} but no "
                    "add_argument in mlcomp_tpu/ or tools/ defines it",
                ))
    return findings


# ---------------------------------------------------------------- driver


def run_passes(root: str = REPO,
               rules: Optional[Set[str]] = None) -> List[Finding]:
    rules = rules or set(ALL_RULES)
    findings: List[Finding] = []
    code_rels = python_files(root, ("mlcomp_tpu", "tools"))
    mods = load_modules(root, code_rels)

    if {"use-after-donate", "donation-vector"} & rules:
        for mi in mods.values():
            findings.extend(check_donation(mi))
    if {"host-sync", "tracer-control-flow", "traced-time"} & rules:
        for rel, mi in mods.items():
            if rel.startswith("tools/"):
                continue  # tools drive engines, they don't trace
            findings.extend(check_trace(mi))
    if {"unguarded-write", "unguarded-read", "bad-annotation"} & rules:
        lock_mods = {
            rel: mi for rel, mi in mods.items() if rel in LOCK_FILES
        }
        findings.extend(check_locks(lock_mods))
    if {"metric-drift", "env-drift", "fault-drift",
            "flag-drift"} & rules:
        findings.extend(check_drift(root, mods))

    # suppressions + bad-suppression findings
    kept: List[Finding] = []
    for f in findings:
        if f.rule not in rules:
            continue
        mi = mods.get(f.path)
        if mi is not None:
            sup = mi.suppress.get(f.line, set())
            if "*" in sup or f.rule in sup:
                continue
        kept.append(f)
    if "bad-suppression" in rules:
        for rel, mi in mods.items():
            for line in mi.bad_suppressions:
                kept.append(Finding(
                    "bad-suppression", rel, line,
                    "graftcheck: ignore[...] without a '-- reason' — "
                    "every suppression must justify itself",
                ))
    seen: Set[Tuple] = set()
    out = []
    for f in sorted(kept, key=Finding.key):
        if f.key() in seen:
            continue
        seen.add(f.key())
        out.append(f)
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftcheck",
        description="JAX-aware static analysis: donation, trace "
        "hazards, lock discipline, artifact drift "
        "(docs/static_analysis.md)",
    )
    ap.add_argument("--root", default=REPO, help="repo root to analyze")
    ap.add_argument(
        "--rules", default=None,
        help="comma-separated rule ids to run (default: all); see "
        "--list-rules",
    )
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as a JSON array")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--list-env", action="store_true",
                    help="dump the env vars the drift pass extracted")
    ap.add_argument("--list-metrics", action="store_true",
                    help="dump the metric families extracted from code")
    ap.add_argument("--list-faults", action="store_true",
                    help="dump the fault points extracted from code")
    args = ap.parse_args(argv)

    if args.list_rules:
        print("\n".join(ALL_RULES))
        return 0
    if args.list_env or args.list_metrics or args.list_faults:
        code = load_modules(args.root, python_files(
            args.root, ("mlcomp_tpu", "tools")
        ))
        if args.list_env:
            for name, sites in sorted(collect_env_vars(code).items()):
                rel, line, kind = sites[0]
                print(f"{name}\t{kind}\t{rel}:{line}")
        if args.list_metrics:
            sel = {
                rel: mi for rel, mi in code.items()
                if rel in ("mlcomp_tpu/engine.py", "mlcomp_tpu/serve.py")
                or rel.startswith("mlcomp_tpu/obs/")
            }
            for name, (rel, line) in sorted(
                collect_code_metrics(sel).items()
            ):
                print(f"{name}\t{rel}:{line}")
        if args.list_faults:
            for p, (rel, line) in sorted(
                collect_fault_points(code).items()
            ):
                print(f"{p}\t{rel}:{line}")
        return 0

    rules: Optional[Set[str]] = None
    if args.rules:
        rules = {r.strip() for r in args.rules.split(",") if r.strip()}
        unknown = rules - set(ALL_RULES)
        if unknown:
            print(f"unknown rule(s): {sorted(unknown)}", file=sys.stderr)
            return 2
    findings = run_passes(args.root, rules)
    if args.as_json:
        print(json.dumps([f.as_dict() for f in findings], indent=2))
    else:
        for f in findings:
            print(f.render())
        n = len(findings)
        print(f"graftcheck: {n} finding{'s' if n != 1 else ''}")
    return 1 if findings else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `graftcheck --list-... | head` is fine
        os._exit(0)
