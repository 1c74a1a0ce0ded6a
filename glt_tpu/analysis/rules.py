"""gltlint rules: the TPU/JAX + concurrency hazards this engine hits.

Each rule is a class with a ``check(module, project=None) -> [Finding]``
method, registered in ``RULES`` by name.  Severities: ERROR findings gate
CI (non-zero exit), WARNINGs report but pass.  ``project`` — the
project-wide symbol table / call graph / effect summaries
(analysis/symbols.py) — is provided whenever the CLI analyzes a file
set; rules use it to follow effects through calls (GLT001/GLT002 become
transitive, GLT008/GLT009 — analysis/concurrency.py — are built on it).
Without a project a rule degrades to its intraprocedural behavior.

The intraprocedural analyses are deliberately linear/flow-light:
statements are walked in source order, ``if`` branches fork analysis
state, loops are traversed once.  That trades soundness for a near-zero
false-positive rate on this codebase — every rule here was calibrated by
running it over ``glt_tpu`` and inspecting each hit.  The
interprocedural layer keeps that bias: unresolvable calls contribute no
effects rather than worst-case guesses.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .effects import (
    COERCIONS,
    DISK_CALLS,
    DISK_READ_METHODS,
    HOST_SYNC_CALLS,
    MMAP_CALLS,
    SYNC_METHODS,
)
from .effects import KEY_SOURCES as _KEY_SOURCES_IMPORTED
from .effects import NON_CONSUMING as _NON_CONSUMING_IMPORTED
from .report import Finding, Severity
from .symbols import FunctionSymbol
from .visitor import (
    JIT_NAMES,
    FunctionScope,
    ModuleInfo,
    assign_targets,
    dotted_expr,
    names_loaded,
    param_names,
    traced_names,
    walk_own,
)

RULES: Dict[str, type] = {}


def register(cls):
    RULES[cls.name] = cls
    return cls


class Rule:
    """Base rule; subclasses set name/code/severity/description."""
    name: str = ""
    code: str = ""
    severity: Severity = Severity.ERROR
    description: str = ""

    def finding(self, module: ModuleInfo, node: ast.AST, message: str
                ) -> Finding:
        return Finding(path=module.path, line=node.lineno,
                       col=node.col_offset + 1, rule=self.name,
                       code=self.code, severity=self.severity,
                       message=message)

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        raise NotImplementedError


# Shared AST helpers live in visitor.py; local aliases keep this module's
# rule bodies terse.
_walk_own = walk_own
_dotted = dotted_expr
_traced_names = traced_names


def _expr_names(node: ast.AST) -> Set[str]:
    """Names + self-attribute dotted strings read inside ``node``."""
    out = names_loaded(node)
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute):
            d = _dotted(n)
            if d is not None:
                out.add(d)
    return out


# ---------------------------------------------------------------------------
# GLT001 host-sync-in-jit
# ---------------------------------------------------------------------------

def compute_jit_taint(module: ModuleInfo
                      ) -> Dict[FunctionScope, Set[str]]:
    """Traced-value sets for every jit-context scope in the module.

    Fixpoint so transitively-jitted helpers see their caller's taint
    (their params are traced only if the call site passes traced values —
    static sizing helpers called with Python config stay clean).
    """
    taint_by_scope: Dict[FunctionScope, Set[str]] = {}
    for _ in range(4):
        changed = False
        for scope in module.scopes:   # DFS order: parents first
            if not module.in_jit_context(scope):
                continue
            taint = _seed_taint(module, scope, taint_by_scope)
            if scope.parent in taint_by_scope:
                taint |= taint_by_scope[scope.parent]
            # two linear passes propagate taint through assignments
            for _ in range(2):
                for node in _walk_own(scope.node):
                    if isinstance(node, (ast.Assign, ast.AnnAssign,
                                         ast.AugAssign)):
                        value = node.value
                        if value is not None and (_traced_names(value)
                                                  & taint):
                            taint |= set(assign_targets(node))
            if taint_by_scope.get(scope) != taint:
                taint_by_scope[scope] = taint
                changed = True
        if not changed:
            break
    return taint_by_scope


def _seed_taint(module: ModuleInfo, scope: FunctionScope,
                taint_by_scope: Dict[FunctionScope, Set[str]]
                ) -> Set[str]:
    """Initial traced-value set: all params for direct jit roots, only
    traced-at-the-call-site params for transitive ones."""
    if scope.transitive_call is None:
        # `self`/`cls` are bound (or closure-captured) at jit time,
        # never traced — counting them floods attribute reads.
        return set(scope.params) - scope.static_args - {"self", "cls"}
    caller, call = scope.transitive_call
    caller_taint = taint_by_scope.get(caller, set())
    params = scope.params
    # bound method call (self.f(...)): positional args bind past self
    if params[:1] == ["self"] and isinstance(call.func, ast.Attribute):
        pos = params[1:]
    else:
        pos = params
    seed: Set[str] = set()
    for i, arg in enumerate(call.args):
        if i < len(pos) and (_traced_names(arg) & caller_taint):
            seed.add(pos[i])
    for kw in call.keywords:
        if kw.arg in params and (_traced_names(kw.value) & caller_taint):
            seed.add(kw.arg)
    return seed - scope.static_args


@register
class HostSyncInJit(Rule):
    """Host transfers/synchronisation on traced values inside jit.

    ``np.asarray``/``np.array``/``jax.device_get``/``.item()``/``int()``/
    ``float()``/``bool()`` on a traced value either fails at trace time
    (TracerArrayConversionError) or — worse, via callbacks — inserts a
    device->host sync into the sampling hot path, serialising the TPU
    against the host exactly as BGL measured for GNN data pipelines.

    With a project, the check is transitive across modules: a call from a
    jit context that passes a traced value into another module's function
    whose effect summary says that parameter reaches a host sync
    (directly or through further calls) is flagged at the call site, with
    the chain in the message.
    """
    name = "host-sync-in-jit"
    code = "GLT001"
    severity = Severity.ERROR
    description = ("numpy conversion / Python scalar coercion of a traced "
                   "value inside a jit/shard_map context (transitive "
                   "through project calls)")

    HOST_CALLS = HOST_SYNC_CALLS
    COERCIONS = COERCIONS
    SYNC_METHODS = SYNC_METHODS

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        taint_by_scope = compute_jit_taint(module)
        for scope in module.scopes:
            if not module.in_jit_context(scope):
                continue
            taint = taint_by_scope.get(scope, set())
            for node in _walk_own(scope.node):
                if not isinstance(node, ast.Call):
                    continue
                findings.extend(self._check_call(module, scope, node, taint))
                if project is not None and taint:
                    findings.extend(self._check_cross_module(
                        module, scope, node, taint, project))
        return findings

    def _check_cross_module(self, module: ModuleInfo, scope: FunctionScope,
                            call: ast.Call, taint: Set[str],
                            project) -> List[Finding]:
        """Follow the call into another module's effect summary."""
        sym = project.resolve_call(module, scope, call)
        if not isinstance(sym, FunctionSymbol) or sym.module is module:
            return []          # same-module helpers: the pass above
        if sym.module.in_jit_context(sym.scope):
            return []          # callee's own module pass reports inside
        summary = project.effects.summary_for(sym)
        sync = summary.sync_param_map()
        if not sync:
            return []
        params = sym.scope.params
        if params[:1] == ["self"] and isinstance(call.func, ast.Attribute):
            pos = params[1:]
        else:
            pos = params
        hits = []
        for i, arg in enumerate(call.args):
            if i < len(pos) and pos[i] in sync \
                    and (_traced_names(arg) & taint):
                hits.append((pos[i], arg))
        for kw in call.keywords:
            if kw.arg in sync and (_traced_names(kw.value) & taint):
                hits.append((kw.arg, kw.value))
        out = []
        for p, arg in hits[:1]:     # one finding per call site
            site = sync[p]
            var = sorted(_traced_names(arg) & taint)[0]
            out.append(self.finding(
                module, call,
                f"traced value '{var}' flows into '{sym.short}' whose "
                f"parameter '{p}' reaches {site.detail} "
                f"({sym.module.path}:{site.line}) — host sync inside jit "
                f"context '{scope.name}'; keep the helper jnp-pure or "
                f"hoist the call to host code"))
        return out

    def _check_call(self, module: ModuleInfo, scope: FunctionScope,
                    call: ast.Call, taint: Set[str]) -> List[Finding]:
        name = module.call_name(call)
        args = list(call.args) + [kw.value for kw in call.keywords]
        touched = set().union(*[_traced_names(a) for a in args]) if args else set()
        where = (f"in jit context '{scope.name}' ({scope.jit_reason})"
                 if scope.jit_reason else f"in jit context '{scope.name}'")
        if name in self.HOST_CALLS and (touched & taint):
            var = sorted(touched & taint)[0]
            return [self.finding(
                module, call,
                f"{name}() on traced value '{var}' {where}: forces a "
                f"device->host transfer (or TracerArrayConversionError); "
                f"use jnp/lax ops instead")]
        if name in self.COERCIONS and (touched & taint):
            var = sorted(touched & taint)[0]
            return [self.finding(
                module, call,
                f"{name}() on traced value '{var}' {where}: concretises "
                f"the tracer (ConcretizationTypeError at trace time); "
                f"hoist to host code or keep it an array")]
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in self.SYNC_METHODS
                and (_traced_names(call.func.value) & taint)):
            var = sorted(_traced_names(call.func.value) & taint)[0]
            return [self.finding(
                module, call,
                f".{call.func.attr}() on traced value '{var}' {where}: "
                f"host sync point inside the compiled program")]
        return []


# ---------------------------------------------------------------------------
# GLT002 prng-key-reuse
# ---------------------------------------------------------------------------

_KEY_SOURCES = _KEY_SOURCES_IMPORTED
# Deriving fresh keys from a base key is the sanctioned way to reuse it.
_NON_CONSUMING = _NON_CONSUMING_IMPORTED
_KEY_PARAM_HINTS = ("key", "rng", "prng")


def _looks_like_key_param(name: str) -> bool:
    low = name.lower()
    return (low in ("key", "rng", "prngkey", "prng_key", "base_key")
            or low.endswith("_key") or low.endswith("_rng")
            or low.endswith("_keys"))


@register
class PrngKeyReuse(Rule):
    """The same PRNG key consumed by two sampling calls.

    jax.random is counter-based: passing one key to two draws yields
    *identical* randomness — on the sampler hot path that silently
    correlates hops/batches (every neighbor draw repeats).  A key may be
    consumed once; reuse requires an intervening ``split``/``fold_in``.

    With a project, call sites resolving to project functions consult the
    callee's effect summary: only arguments bound to parameters the
    callee actually consumes as keys (directly or transitively) count as
    consumption — a helper that merely ``split``s its key argument is as
    safe as ``jax.random.split`` itself, and a consuming helper two
    modules away still burns the key.  Unresolvable calls keep the
    conservative behavior (any call consumes).
    """
    name = "prng-key-reuse"
    code = "GLT002"
    severity = Severity.ERROR
    description = ("a PRNG key passed to two consuming calls (callee "
                   "effect summaries decide consumption) without an "
                   "intervening jax.random.split/fold_in")

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        self._project = project
        for scope in module.scopes:
            if isinstance(scope.node, ast.Lambda):
                continue
            self._scope = scope
            state: Dict[str, int] = {
                p: 0 for p in scope.params if _looks_like_key_param(p)}
            self._run(module, scope.node.body, state, findings)
        return findings

    # -- branch-aware linear interpreter ----------------------------------
    def _run(self, module: ModuleInfo, body: Sequence[ast.stmt],
             state: Dict[str, int], findings: List[Finding]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, ast.If):
                s1, s2 = dict(state), dict(state)
                self._run(module, stmt.body, s1, findings)
                self._run(module, stmt.orelse, s2, findings)
                # conservative merge: a use must happen on *every* path to
                # count against later statements
                state.clear()
                for var in set(s1) & set(s2):
                    state[var] = min(s1[var], s2[var])
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                self._visit_exprs(module, stmt, state, findings,
                                  skip_body=True)
                self._run(module, stmt.body, state, findings)
                self._run(module, stmt.orelse, state, findings)
                continue
            if isinstance(stmt, ast.Try):
                self._run(module, stmt.body, state, findings)
                for h in stmt.handlers:
                    self._run(module, h.body, dict(state), findings)
                self._run(module, stmt.orelse, state, findings)
                self._run(module, stmt.finalbody, state, findings)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                self._visit_exprs(module, stmt, state, findings,
                                  skip_body=True)
                self._run(module, stmt.body, state, findings)
                continue
            self._visit_exprs(module, stmt, state, findings)
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                self._apply_assign(module, stmt, state)

    def _consuming_arg_ids(self, module: ModuleInfo,
                           node: ast.Call) -> Optional[Set[int]]:
        """With a resolved callee summary: the ``id()``s of the argument
        nodes bound to key-consuming parameters.  None means the call is
        unresolvable — treat every argument as consuming (conservative)."""
        if self._project is None:
            return None
        sym = self._project.resolve_call(module, self._scope, node)
        if not isinstance(sym, FunctionSymbol):
            return None
        summary = self._project.effects.summary_for(sym)
        params = sym.scope.params
        if params[:1] == ["self"] and isinstance(node.func, ast.Attribute):
            pos = params[1:]
        else:
            pos = params
        consuming: Set[int] = set()
        for i, arg in enumerate(node.args):
            if i < len(pos) and pos[i] in summary.key_params:
                consuming.add(id(arg))
        for kw in node.keywords:
            if kw.arg in summary.key_params:
                consuming.add(id(kw.value))
        return consuming

    def _visit_exprs(self, module: ModuleInfo, stmt: ast.stmt,
                     state: Dict[str, int], findings: List[Finding],
                     skip_body: bool = False) -> None:
        nodes: Iterator[ast.AST]
        if skip_body:
            nodes = iter(())
            for field in ("test", "iter", "items", "target"):
                sub = getattr(stmt, field, None)
                if sub is not None:
                    sub_list = sub if isinstance(sub, list) else [sub]
                    nodes = iter(list(nodes) + [
                        n for s in sub_list
                        for n in ast.walk(s if not hasattr(s, "context_expr")
                                          else s.context_expr)])
        else:
            nodes = _walk_own(stmt)
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            name = module.call_name(node)
            if name in _NON_CONSUMING:
                continue
            consuming = self._consuming_arg_ids(module, node)
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name) and arg.id in state:
                    if consuming is not None and id(arg) not in consuming:
                        continue     # callee provably derives, not draws
                    state[arg.id] += 1
                    if state[arg.id] == 2:
                        findings.append(self.finding(
                            module, node,
                            f"PRNG key '{arg.id}' consumed a second time "
                            f"(same randomness as its first use); derive a "
                            f"fresh key with jax.random.split/fold_in "
                            f"before this call"))

    def _apply_assign(self, module: ModuleInfo, stmt: ast.stmt,
                      state: Dict[str, int]) -> None:
        targets = assign_targets(stmt)
        value = getattr(stmt, "value", None)
        is_key_src = (isinstance(value, ast.Call)
                      and module.call_name(value) in _KEY_SOURCES)
        for t in targets:
            if is_key_src:
                state[t] = 0            # fresh key: uses reset
            elif t in state:
                del state[t]            # overwritten with a non-key value


# ---------------------------------------------------------------------------
# GLT003 recompile-hazard
# ---------------------------------------------------------------------------

@register
class RecompileHazard(Rule):
    """Python scalars closure-captured into a jit target.

    ``jax.jit(lambda x: x * n)`` bakes ``n`` into the traced program as a
    compile-time constant: every distinct value of ``n`` (a batch width, a
    ``.shape[0]``, a fanout) triggers a full recompile — the PyGraph
    failure mode, silent on TPU until the profile shows nothing but
    compilation.  Pass the scalar as a (possibly static) argument instead.
    """
    name = "recompile-hazard"
    code = "GLT003"
    severity = Severity.WARNING
    description = ("a Python scalar captured by a jitted closure without "
                   "static_argnums/static_argnames")

    _SCALAR_CALLS = {"int", "float", "len", "round", "min", "max"}

    def check(self, module: ModuleInfo, project=None
              ) -> List[Finding]:
        findings: List[Finding] = []
        for scope in module.scopes:
            if isinstance(scope.node, ast.Lambda):
                continue
            scalars = self._scalar_locals(module, scope)
            if not scalars:
                continue
            for node in _walk_own(scope.node):
                if not isinstance(node, ast.Call):
                    continue
                if module.call_name(node) not in JIT_NAMES:
                    continue
                findings.extend(
                    self._check_jit_call(module, scope, node, scalars))
        return findings

    def _scalar_locals(self, module: ModuleInfo, scope: FunctionScope
                       ) -> Set[str]:
        """Locals assigned from obviously-Python-scalar expressions."""
        scalars: Set[str] = set()
        for node in _walk_own(scope.node):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            if node.value is not None and self._is_scalarish(module,
                                                             node.value):
                scalars |= set(assign_targets(node))
        return scalars

    def _is_scalarish(self, module: ModuleInfo, expr: ast.expr) -> bool:
        if isinstance(expr, ast.Constant):
            return isinstance(expr.value, (int, float)) and not isinstance(
                expr.value, bool)
        if isinstance(expr, ast.Call):
            return module.call_name(expr) in self._SCALAR_CALLS
        if isinstance(expr, ast.Attribute):
            return expr.attr in ("shape", "ndim", "size")
        if isinstance(expr, ast.Subscript):
            return (isinstance(expr.value, ast.Attribute)
                    and expr.value.attr == "shape")
        if isinstance(expr, ast.BinOp):
            return (self._is_scalarish(module, expr.left)
                    or self._is_scalarish(module, expr.right))
        return False

    def _check_jit_call(self, module: ModuleInfo, scope: FunctionScope,
                        call: ast.Call, scalars: Set[str]) -> List[Finding]:
        has_static = any(kw.arg in ("static_argnums", "static_argnames")
                         for kw in call.keywords)
        if has_static or not call.args:
            return []
        target = call.args[0]
        fn_node = None
        if isinstance(target, ast.Lambda):
            fn_node = target
        elif isinstance(target, ast.Name):
            for child in module.scopes:
                if (child.parent is scope and child.name == target.id
                        and not isinstance(child.node, ast.Lambda)):
                    fn_node = child.node
                    break
        if fn_node is None:
            return []
        body = (fn_node.body if isinstance(fn_node, ast.Lambda)
                else fn_node)
        free = names_loaded(body) - set(param_names(fn_node))
        if not isinstance(fn_node, ast.Lambda):
            for node in _walk_own(fn_node):
                if isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign)):
                    free -= set(assign_targets(node))
        captured = sorted(free & scalars)
        if not captured:
            return []
        return [self.finding(
            module, call,
            f"jit target closes over Python scalar(s) "
            f"{', '.join(repr(c) for c in captured)}: each distinct value "
            f"recompiles the program; pass as an argument (traced) or mark "
            f"static_argnums/static_argnames")]


# ---------------------------------------------------------------------------
# GLT004 int64-id-truncation
# ---------------------------------------------------------------------------

@register
class Int64IdTruncation(Rule):
    """int64 node/edge ids fed to jnp without an explicit dtype.

    JAX disables x64 by default: ``jnp.asarray(ids_int64)`` silently
    truncates to int32.  Ids above 2**31 (papers100M edge ids already
    qualify) wrap negative and index garbage rows.  Either pass an
    explicit dtype (acknowledging the narrowing) or relabel ids into
    int32 range first.
    """
    name = "int64-id-truncation"
    code = "GLT004"
    severity = Severity.ERROR
    description = ("np.int64 values flowing into jnp.asarray/array with no "
                   "explicit dtype (silent int32 truncation under default "
                   "x64-disabled JAX)")

    _SINKS = {"jax.numpy.asarray", "jax.numpy.array"}

    def check(self, module: ModuleInfo, project=None
              ) -> List[Finding]:
        findings: List[Finding] = []
        module_taint = self._collect_taint(module, module.tree, set())
        self._scan(module, module.tree, module_taint, findings,
                   skip_scopes=True)
        for scope in module.scopes:
            taint = self._collect_taint(module, scope.node,
                                        set(module_taint))
            self._scan(module, scope.node, taint, findings,
                       skip_scopes=False)
        return findings

    def _collect_taint(self, module: ModuleInfo, root: ast.AST,
                       seed: Set[str]) -> Set[str]:
        taint = set(seed)
        for _ in range(2):
            for node in _walk_own(root):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                if node.value is None:
                    continue
                if (self._is_int64_expr(module, node.value)
                        or self._propagates(module, node.value, taint)):
                    for t in assign_targets(node):
                        taint.add(t)
                    # also self.x targets
                    tgts = (node.targets if isinstance(node, ast.Assign)
                            else [node.target])
                    for t in tgts:
                        d = _dotted(t)
                        if d is not None and "." in d:
                            taint.add(d)
        return taint

    def _propagates(self, module: ModuleInfo, expr: ast.expr,
                    taint: Set[str]) -> bool:
        """Does int64-ness flow from a tainted name into this value?

        Structural operations (copies, indexing, arithmetic, ``np.*``
        reshuffles, ``.reshape()``-style methods on tainted values) keep
        the dtype; results of arbitrary user functions do not inherit it
        — assuming they did floods every consumer of an id array.
        Comparisons/boolean ops yield bools, never ids.
        """
        if isinstance(expr, (ast.Name, ast.Attribute)):
            d = _dotted(expr)
            return d in taint if d is not None else False
        if isinstance(expr, ast.Subscript):
            return self._propagates(module, expr.value, taint)
        if isinstance(expr, ast.BinOp):
            return (self._propagates(module, expr.left, taint)
                    or self._propagates(module, expr.right, taint))
        if isinstance(expr, ast.UnaryOp):
            return self._propagates(module, expr.operand, taint)
        if isinstance(expr, ast.IfExp):
            return (self._propagates(module, expr.body, taint)
                    or self._propagates(module, expr.orelse, taint))
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self._propagates(module, el, taint)
                       for el in expr.elts)
        if isinstance(expr, ast.Starred):
            return self._propagates(module, expr.value, taint)
        if isinstance(expr, ast.Call):
            name = module.call_name(expr) or ""
            args = list(expr.args) + [kw.value for kw in expr.keywords]
            any_tainted = any(self._propagates(module, a, taint)
                              for a in args)
            if name.startswith("numpy.") and not name.startswith(
                    "numpy.random."):
                return any_tainted
            # dtype-preserving method on a tainted value: x.reshape(...)
            if (isinstance(expr.func, ast.Attribute)
                    and expr.func.attr in ("reshape", "ravel", "copy",
                                           "flatten", "squeeze",
                                           "transpose", "take", "clip")
                    and self._propagates(module, expr.func.value, taint)):
                return True
            return False
        return False

    def _is_int64_expr(self, module: ModuleInfo, expr: ast.expr) -> bool:
        """Does the expression *introduce* int64-ness?"""
        for node in ast.walk(expr):
            if isinstance(node, ast.Attribute):
                if module.imports.resolve(node) in ("numpy.int64",
                                                    "numpy.uint64"):
                    return True
            if isinstance(node, ast.Call):
                # .astype(np.int64) / .astype("int64")
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "astype" and node.args):
                    a = node.args[0]
                    if (module.imports.resolve(a) in ("numpy.int64",
                                                      "numpy.uint64")
                            or (isinstance(a, ast.Constant)
                                and a.value in ("int64", "uint64"))):
                        return True
                # np.*(..., dtype=np.int64)
                for kw in node.keywords:
                    if kw.arg == "dtype" and (
                            module.imports.resolve(kw.value)
                            in ("numpy.int64", "numpy.uint64")
                            or (isinstance(kw.value, ast.Constant)
                                and kw.value.value in ("int64", "uint64"))):
                        return True
        return False

    def _scan(self, module: ModuleInfo, root: ast.AST, taint: Set[str],
              findings: List[Finding], skip_scopes: bool) -> None:
        walker = (_walk_own(root) if skip_scopes else ast.walk(root))
        for node in walker:
            if not isinstance(node, ast.Call):
                continue
            if module.call_name(node) not in self._SINKS:
                continue
            if len(node.args) >= 2:            # positional dtype
                continue
            if any(kw.arg == "dtype" for kw in node.keywords):
                continue
            if not node.args:
                continue
            arg = node.args[0]
            hit = self._is_int64_expr(module, arg)
            tainted = (sorted(_expr_names(arg) & taint)
                       if self._propagates(module, arg, taint) else [])
            if hit or tainted:
                what = (f"'{tainted[0]}'" if tainted
                        else "an int64 expression")
                findings.append(self.finding(
                    module, node,
                    f"jnp conversion of int64 ids ({what}) without an "
                    f"explicit dtype: silently truncates to int32 under "
                    f"default x64-disabled JAX; pass dtype= (or relabel "
                    f"into int32 range first)"))


# ---------------------------------------------------------------------------
# GLT005 nondeterministic-default-rng
# ---------------------------------------------------------------------------

@register
class NondeterministicDefaultRng(Rule):
    """Unseeded ``np.random.default_rng()`` in library code.

    OS-entropy seeding makes sampling unreproducible across runs and —
    worse on a pod — *divergent across hosts*, so "identical" per-host
    programs sample different subgraphs and collective shapes drift.
    Always seed from configuration (and fold in the epoch/host index).
    """
    name = "nondeterministic-default-rng"
    code = "GLT005"
    severity = Severity.WARNING
    description = "np.random.default_rng() with no seed argument"

    _RNG = {"numpy.random.default_rng", "numpy.random.Generator",
            "numpy.random.RandomState"}

    def check(self, module: ModuleInfo, project=None
              ) -> List[Finding]:
        findings: List[Finding] = []
        # fresh-generator-inline-draw: default_rng(seed).permutation(x)
        # where `seed` is a parameter of the enclosing function replays
        # the identical stream on every call — the repeated-permutation-
        # across-epochs bug class (a constant literal seed is a one-shot
        # deterministic fixture; a per-call-varying seed expression is a
        # deliberate stream; a bare parameter is the same value every
        # call of this function).
        for scope in module.scopes:
            params = set(scope.params)
            for node in _walk_own(scope.node):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and isinstance(node.func.value, ast.Call)
                        and module.call_name(node.func.value) in self._RNG
                        and node.func.value.args):
                    continue
                seed_arg = node.func.value.args[0]
                if (isinstance(seed_arg, ast.Name)
                        and seed_arg.id in params):
                    findings.append(self.finding(
                        module, node,
                        f"fresh Generator from parameter "
                        f"'{seed_arg.id}' drawn inline "
                        f"(.{node.func.attr}()): every call of "
                        f"'{scope.name}' replays the identical stream — "
                        f"across epochs that repeats the exact "
                        f"permutation; thread a stateful Generator "
                        f"through instead"))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = module.call_name(node)
            if name not in self._RNG:
                continue
            unseeded = not node.args and not node.keywords
            if not unseeded and node.args:
                a = node.args[0]
                unseeded = isinstance(a, ast.Constant) and a.value is None
            if unseeded:
                findings.append(self.finding(
                    module, node,
                    f"{name}() without a seed: draws from OS entropy — "
                    f"unreproducible, and divergent across pod hosts; "
                    f"thread a seeded Generator through instead"))
        return findings


# ---------------------------------------------------------------------------
# GLT006 shadowed-jit-donation
# ---------------------------------------------------------------------------

@register
class ShadowedJitDonation(Rule):
    """A buffer read again after being donated to a jitted call.

    ``donate_argnums`` hands the argument's buffer to XLA for reuse; the
    original array is *deleted*.  A later read raises
    RuntimeError("Array has been deleted") on TPU — but passes silently
    on CPU backends where donation is a no-op, so only the lint (or the
    pod) catches it.
    """
    name = "shadowed-jit-donation"
    code = "GLT006"
    severity = Severity.ERROR
    description = ("an array used again after being passed through "
                   "donate_argnums")

    def check(self, module: ModuleInfo, project=None
              ) -> List[Finding]:
        donors = self._collect_donors(module)
        if not donors:
            return []
        findings: List[Finding] = []
        for scope in module.scopes:
            if isinstance(scope.node, ast.Lambda):
                continue
            self._run(module, scope.node.body, donors, {}, findings)
        self._run(module, module.tree.body, donors, {}, findings)
        return findings

    def _collect_donors(self, module: ModuleInfo) -> Dict[str, Set[int]]:
        """callable name -> donated positional indices (module-wide)."""
        donors: Dict[str, Set[int]] = {}
        for scope in module.scopes:
            if scope.donate_argnums:
                donors[scope.name] = set(scope.donate_argnums)
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = getattr(node, "value", None)
            if not (isinstance(value, ast.Call)
                    and module.call_name(value) in JIT_NAMES):
                continue
            donated = {el for kw in value.keywords
                       if kw.arg == "donate_argnums"
                       for el in _iter_const_ints(kw.value)}
            if not donated:
                continue
            tgts = (node.targets if isinstance(node, ast.Assign)
                    else [node.target])
            for t in tgts:
                d = _dotted(t)
                if d is not None:
                    donors[d] = set(donated)
        return donors

    def _run(self, module: ModuleInfo, body: Sequence[ast.stmt],
             donors: Dict[str, Set[int]],
             dead: Dict[str, Tuple[int, str]],
             findings: List[Finding]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, ast.If):
                s1, s2 = dict(dead), dict(dead)
                self._run(module, stmt.body, donors, s1, findings)
                self._run(module, stmt.orelse, donors, s2, findings)
                dead.clear()
                dead.update(s1)
                dead.update(s2)      # dead on either path counts
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While,
                                 ast.With, ast.AsyncWith, ast.Try)):
                for sub in (getattr(stmt, "body", []) or []):
                    self._run(module, [sub], donors, dead, findings)
                for sub in (getattr(stmt, "orelse", []) or []):
                    self._run(module, [sub], donors, dead, findings)
                for h in getattr(stmt, "handlers", ()) or ():
                    self._run(module, h.body, donors, dict(dead), findings)
                for sub in (getattr(stmt, "finalbody", []) or []):
                    self._run(module, [sub], donors, dead, findings)
                continue
            # 1) reads of already-donated buffers (before this statement's
            #    own donation processing)
            donating_calls = [n for n in _walk_own(stmt)
                              if isinstance(n, ast.Call)
                              and self._donor_name(n, donors) is not None]
            donated_arg_nodes: Set[int] = set()
            for call in donating_calls:
                name = self._donor_name(call, donors)
                for idx in donors[name]:
                    if idx < len(call.args) and isinstance(call.args[idx],
                                                           ast.Name):
                        donated_arg_nodes.add(id(call.args[idx]))
            for node in _walk_own(stmt):
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)
                        and node.id in dead
                        and id(node) not in donated_arg_nodes):
                    line, fn = dead[node.id]
                    findings.append(self.finding(
                        module, node,
                        f"'{node.id}' used after being donated to "
                        f"'{fn}' (line {line}): donated buffers are "
                        f"deleted on TPU (RuntimeError); copy first or "
                        f"drop the reuse"))
                    del dead[node.id]          # report once per donation
            # 2) this statement's donations
            for call in donating_calls:
                name = self._donor_name(call, donors)
                for idx in donors[name]:
                    if idx < len(call.args) and isinstance(call.args[idx],
                                                           ast.Name):
                        dead[call.args[idx].id] = (call.lineno, name)
            # 3) reassignments resurrect
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                for t in assign_targets(stmt):
                    dead.pop(t, None)

    @staticmethod
    def _donor_name(call: ast.Call, donors: Dict[str, Set[int]]
                    ) -> Optional[str]:
        d = _dotted(call.func)
        return d if d in donors else None


# ---------------------------------------------------------------------------
# GLT007 unbounded-blocking-get
# ---------------------------------------------------------------------------

@register
class UnboundedBlockingGet(Rule):
    """``queue.Queue.get()`` / ``Thread.join()`` that can block forever.

    The distributed hang class: a consumer blocked in a no-timeout
    ``.get()`` waits forever once its producer thread/process dies between
    its last put and the get — nothing will ever arrive, and nothing
    raises.  Same shape for a no-timeout ``.join()`` on a thread wedged on
    a bounded queue.  Library code must either bound the wait (``timeout=``)
    or recheck liveness while polling (``channel.base.bounded_get``); a
    wait proven bounded by construction takes a justified suppression.
    """
    name = "unbounded-blocking-get"
    code = "GLT007"
    severity = Severity.ERROR
    description = ("a blocking .get()/.join() call with no timeout and no "
                   "liveness recheck in the enclosing function")

    # Zero-argument spellings only: dict.get(key), "".join(parts),
    # thread.join(5) all carry arguments and are not the blocking form.
    _BLOCKING = {"get", "join"}
    # A scope that probes peer liveness is running the timeout-and-recheck
    # pattern; its waits are bounded by the recheck loop.
    _LIVENESS = {"is_alive", "is_set", "poll"}

    def check(self, module: ModuleInfo, project=None
              ) -> List[Finding]:
        findings: List[Finding] = []
        regions = [module.tree] + [
            s.node for s in module.scopes
            if not isinstance(s.node, ast.Lambda)]
        for node in regions:
            calls = [n for n in _walk_own(node)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Attribute)]
            if any(c.func.attr in self._LIVENESS for c in calls):
                continue
            for call in calls:
                if (call.func.attr in self._BLOCKING
                        and not call.args and not call.keywords):
                    findings.append(self.finding(
                        module, call,
                        f".{call.func.attr}() with no timeout and no "
                        f"liveness check in scope: blocks forever if the "
                        f"producer/thread died — pass timeout= in a "
                        f"recheck loop (see channel.base.bounded_get), or "
                        f"suppress with a bounded-wait justification"))
        return findings


# ---------------------------------------------------------------------------
# GLT010 span-in-traced-code
# ---------------------------------------------------------------------------

@register
class SpanInTracedCode(Rule):
    """``glt_tpu.obs`` span/metric host calls inside jit-traced functions.

    The obs library is host-side: a ``span()`` / ``Counter.inc()`` inside
    a jit-traced function executes ONCE at trace time and then vanishes
    from the compiled program — the span measures tracing, the counter
    counts compilations, and both silently stop moving as soon as the
    cached executable is reused.  Instrument at the host call boundary
    (loaders, epoch drivers, dispatch wrappers) and fence device work
    with ``span.fence(out)`` instead.

    Flagged spellings, inside any scope :meth:`ModuleInfo.in_jit_context`
    marks traced:

      * any call resolving (through the import map) into ``glt_tpu.obs``
        — ``span(...)``, ``obs.span(...)``, ``metrics.counter(...)``;
      * ``.inc()/.observe()/.set()/.time()/.fence()`` on a name assigned
        from an obs factory in this module (module-level ``_M = ...`` or
        ``self._m = ...`` instruments) or chained directly off one
        (``metrics.counter("x").inc()``).

    ``.at[i].set(v)`` and other non-obs receivers never match: the
    receiver must trace back to an obs import or an obs-built name.

    ``glt_tpu.obs.scopes`` is the one part of obs made for traced code
    (device scopes: ``jax.named_scope`` under the ``glt.`` taxonomy, kept
    by the compiled program as metadata) and never matches.
    """
    name = "span-in-traced-code"
    code = "GLT010"
    severity = Severity.ERROR
    description = ("glt_tpu.obs span/metric call inside a jit-traced "
                   "function (host side effects vanish under trace)")

    _OBS_PREFIX = "glt_tpu.obs"
    _DEVICE_SCOPES = "glt_tpu.obs.scopes"
    _METHODS = {"inc", "observe", "set", "time", "fence"}

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        instruments = self._instrument_names(module)
        findings: List[Finding] = []
        for scope in module.scopes:
            if not module.in_jit_context(scope):
                continue
            for node in _walk_own(scope.node):
                if not isinstance(node, ast.Call):
                    continue
                message = self._obs_call(module, node, instruments)
                if message:
                    findings.append(self.finding(module, node, message))
        return findings

    def _is_obs_path(self, dotted: Optional[str]) -> bool:
        return bool(dotted) and (
            dotted == self._OBS_PREFIX
            or dotted.startswith(self._OBS_PREFIX + ".")) and not (
            dotted == self._DEVICE_SCOPES
            or dotted.startswith(self._DEVICE_SCOPES + "."))

    def _instrument_names(self, module: ModuleInfo) -> Set[str]:
        """Names (plain or ``self.x`` dotted) assigned from an obs
        factory call anywhere in the module."""
        out: Set[str] = set()
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = node.value
            if not (isinstance(value, ast.Call)
                    and self._is_obs_path(module.call_name(value))):
                continue
            out |= set(assign_targets(node))
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                d = _dotted(t)
                if d:
                    out.add(d)
        return out

    def _obs_call(self, module: ModuleInfo, call: ast.Call,
                  instruments: Set[str]) -> Optional[str]:
        resolved = module.call_name(call)
        if self._is_obs_path(resolved):
            return (f"{resolved}() inside a jit-traced function: the host "
                    f"call runs once at trace time and vanishes from the "
                    f"compiled program — instrument the host dispatch "
                    f"loop instead (span.fence(out) observes device time)")
        func = call.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in self._METHODS):
            return None
        receiver = _dotted(func.value)
        if receiver is not None and receiver in instruments:
            return (f".{func.attr}() on obs instrument {receiver!r} "
                    f"inside a jit-traced function: the host side effect "
                    f"vanishes under trace — move it to the host loop")
        inner = func.value
        while isinstance(inner, ast.Attribute):
            inner = inner.value
        if (isinstance(inner, ast.Call)
                and self._is_obs_path(module.call_name(inner))):
            return (f".{func.attr}() chained off an obs factory inside a "
                    f"jit-traced function: the host side effect vanishes "
                    f"under trace — move it to the host loop")
        return None


# ---------------------------------------------------------------------------
# GLT011 non-atomic-state-publish
# ---------------------------------------------------------------------------

@register
class NonAtomicStatePublish(Rule):
    """``open(path, "w")`` publishing state without tmp + ``os.replace``.

    The durable-state discipline (glt_tpu.ckpt.store, channel/native.py):
    anything another process may read — checkpoints, manifests, trace
    exports, bench/report artifacts — is written fully under a private
    tmp name and published with ONE atomic rename.  A direct write to
    the final path is a torn-read window: a reader (or a crash) midway
    through the write observes a half-written file that parses as
    garbage or, worse, parses cleanly as truncated state.

    Flagged: ``open()`` in write/create mode (``w``/``x``/``a`` modes)
    on a path that is not visibly a tmp name (no ``tmp``/``temp`` in the
    path expression), in an enclosing function that never publishes via
    ``os.replace``/``os.rename``/``shutil.move``.  A function that does
    rename-publish is trusted for all its writes (the tmp file it writes
    may be named by any expression); genuinely process-private files
    take a tmp-ish name or a justified suppression.
    """
    name = "non-atomic-state-publish"
    code = "GLT011"
    severity = Severity.ERROR
    description = ("direct open(path, 'w') write without the tmp + "
                   "os.replace atomic-publish discipline")

    _PUBLISH = {"os.replace", "os.rename", "shutil.move"}
    _WRITE_MODES = ("w", "x", "a")

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        regions = [module.tree] + [
            s.node for s in module.scopes
            if not isinstance(s.node, ast.Lambda)]
        for region in regions:
            calls = [n for n in _walk_own(region)
                     if isinstance(n, ast.Call)]
            if any((module.call_name(c) or _dotted(c.func))
                   in self._PUBLISH for c in calls):
                continue
            for call in calls:
                mode = self._write_mode(call)
                if mode is None:
                    continue
                path_src = ast.unparse(call.args[0]) if call.args else ""
                low = path_src.lower()
                if "tmp" in low or "temp" in low:
                    continue
                findings.append(self.finding(
                    module, call,
                    f"open({path_src}, {mode!r}) writes the final path "
                    f"directly: a reader (or this process, killed "
                    f"mid-write) can observe a torn file — write to a "
                    f".tmp- sibling and publish with one os.replace "
                    f"(the glt_tpu.ckpt.store discipline), or name the "
                    f"path tmp-ish if it is truly process-private"))
        return findings

    def _write_mode(self, call: ast.Call) -> Optional[str]:
        if not (isinstance(call.func, ast.Name)
                and call.func.id == "open" and call.args):
            return None
        mode = None
        if len(call.args) >= 2:
            mode = call.args[1]
        for kw in call.keywords:
            if kw.arg == "mode":
                mode = kw.value
        if not (isinstance(mode, ast.Constant)
                and isinstance(mode.value, str)):
            return None
        return (mode.value if any(ch in mode.value
                                  for ch in self._WRITE_MODES) else None)


# ---------------------------------------------------------------------------
# GLT012 unbounded-queue-put
# ---------------------------------------------------------------------------

@register
class UnboundedQueuePut(Rule):
    """``queue.Queue()`` built without a ``maxsize`` bound.

    The backpressure hole the serving/server paths must not have: an
    unbounded queue between a fast producer (accepting connections,
    admitting requests) and a slower consumer grows until the process
    OOMs — under overload the correct behavior is a bounded queue whose
    ``put_nowait``/``Full`` turns into a structured ``Overloaded``
    rejection (glt_tpu.serving.front) or a stop-aware ``bounded_put``
    (channel.base).  Flags ``queue.Queue()`` / ``LifoQueue`` /
    ``PriorityQueue`` constructed with no ``maxsize`` (or an explicit
    ``maxsize<=0``, which stdlib treats as infinite), and
    ``queue.SimpleQueue()`` (unboundable by design).  Multiprocessing
    queues are out of scope: they are sized by their pipe buffers and
    used as small task queues here.
    """
    name = "unbounded-queue-put"
    code = "GLT012"
    severity = Severity.ERROR
    description = ("queue.Queue() constructed without a positive maxsize "
                   "bound (unbounded growth under backpressure)")

    _BOUNDED_CLASSES = {"queue.Queue", "queue.LifoQueue",
                        "queue.PriorityQueue"}
    _UNBOUNDABLE = {"queue.SimpleQueue"}

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = module.call_name(node)
            if name in self._UNBOUNDABLE:
                findings.append(self.finding(
                    module, node,
                    f"{name}() cannot be bounded: under backpressure it "
                    f"grows without limit — use queue.Queue(maxsize=N) "
                    f"with put_nowait -> structured rejection instead"))
                continue
            if name not in self._BOUNDED_CLASSES:
                continue
            size = None
            if node.args:
                size = node.args[0]
            for kw in node.keywords:
                if kw.arg == "maxsize":
                    size = kw.value
            if size is None:
                findings.append(self.finding(
                    module, node,
                    f"{name}() without maxsize is unbounded: a stalled "
                    f"consumer lets it grow until OOM — pass "
                    f"maxsize=<bound> and handle queue.Full as "
                    f"backpressure (reject/drop), or justify with a "
                    f"suppression"))
            elif (isinstance(size, ast.Constant)
                    and isinstance(size.value, int) and size.value <= 0):
                findings.append(self.finding(
                    module, node,
                    f"{name}(maxsize={size.value}) is the unbounded "
                    f"spelling (stdlib treats <=0 as infinite); pass a "
                    f"positive bound"))
        return findings


# ---------------------------------------------------------------------------
# GLT013 dispatch-in-epoch-loop
# ---------------------------------------------------------------------------

@register
class DispatchInEpochLoop(Rule):
    """Per-batch host round-trips inside an epoch driver's batch loop.

    The fused-epoch contract (docs/architecture.md "The fused
    epoch"): an epoch driver dispatches compiled programs and fetches
    device values ONCE at the epoch boundary — a device->host fetch
    (``jax.device_get`` / ``np.asarray`` / ``.item()`` /
    ``block_until_ready`` / ``int()``/``float()`` coercions) inside the
    per-batch loop puts a host round trip on every batch's critical
    path and silently reverts the scanned route to serialized per-batch
    latency (bench.py's serialized vs pipelined split).  This is the
    static guard that keeps the fusion win
    from regressing.

    Scope (calibrated on this tree): ``for``/``while`` bodies of
    functions named ``run_*epoch*`` — the epoch-driver naming
    convention (``run_scanned_epoch``, ``run_scanned_dist_epoch``,
    ``_ColdStagePipeline.run_epoch``).  Direct fetches are always
    flagged; with a project, calls into helpers whose effect summary
    reaches a host sync are flagged too (the round trip hidden one call
    deep).  Deliberate syncs — a checkpoint hook that must capture
    post-block-exact state — carry a justified suppression.
    """
    name = "dispatch-in-epoch-loop"
    code = "GLT013"
    severity = Severity.ERROR
    description = ("device->host fetch inside an epoch driver's batch "
                   "loop (per-batch host round trip on the critical "
                   "path)")

    _EPOCH_NAME = "epoch"
    _EPOCH_PREFIXES = ("run_", "_run_")
    _FETCH_CALLS = (set(HOST_SYNC_CALLS)
                    | {"jax.block_until_ready", "jax.device_get"})

    @classmethod
    def _is_epoch_driver(cls, name: str) -> bool:
        return (cls._EPOCH_NAME in name
                and name.startswith(cls._EPOCH_PREFIXES))

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        for scope in module.scopes:
            if not self._is_epoch_driver(scope.name):
                continue
            for loop in _walk_own(scope.node):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                for node in ast.walk(loop):
                    if isinstance(node, ast.Call):
                        f = self._check_call(module, scope, node, project)
                        if f is not None:
                            findings.append(f)
        return findings

    def _check_call(self, module: ModuleInfo, scope, call: ast.Call,
                    project) -> Optional[Finding]:
        name = module.call_name(call)
        if name in self._FETCH_CALLS:
            return self.finding(
                module, call,
                f"'{name}' inside the batch loop of epoch driver "
                f"'{scope.name}' fetches device state every batch — "
                f"accumulate device values and fetch ONCE after the "
                f"loop (one concat + one host read), or justify with a "
                f"suppression")
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in SYNC_METHODS):
            return self.finding(
                module, call,
                f".{call.func.attr}() inside the batch loop of epoch "
                f"driver '{scope.name}' is a per-batch device sync — "
                f"hoist the fetch out of the loop or justify with a "
                f"suppression")
        if name in COERCIONS and call.args \
                and not isinstance(call.args[0], ast.Constant):
            return self.finding(
                module, call,
                f"'{name}(...)' inside the batch loop of epoch driver "
                f"'{scope.name}': coercing a device value is a blocking "
                f"fetch per batch — keep losses as device arrays and "
                f"reduce once after the loop")
        # One call deep: a helper whose effect summary reaches a host
        # sync (project-wide pass only).
        if project is not None:
            sym = project.resolve_call(module, scope, call)
            if isinstance(sym, FunctionSymbol):
                summary = project.effects.summary_for(sym)
                sync = summary.sync_param_map()
                if sync:
                    p, site = next(iter(sorted(sync.items())))
                    return self.finding(
                        module, call,
                        f"'{sym.short}' called in the batch loop of "
                        f"epoch driver '{scope.name}' reaches a host "
                        f"sync through parameter '{p}' "
                        f"({sym.module.path}:{site.line}) — a hidden "
                        f"per-batch round trip; fetch after the epoch "
                        f"instead")
        return None


# ---------------------------------------------------------------------------
# GLT014 blocking-io-in-epoch-loop
# ---------------------------------------------------------------------------

@register
class BlockingIOInEpochLoop(Rule):
    """Synchronous disk reads inside an epoch driver's batch loop.

    The disk tier's contract (docs/storage.md): storage I/O belongs on
    the DRAM stager's background threads, hinted ahead of the sampler —
    a synchronous read (``np.load``/``np.fromfile``, slicing a
    ``np.memmap``, a file object's ``.read()``) inside the per-batch
    loop of a ``run_*epoch*`` driver puts device-idle milliseconds on
    every batch: the demand-fault path the stage-ahead hook exists to
    avoid.  Staging threads are out of scope by construction — they are
    not epoch drivers.

    Direct reads are always flagged; with a project, calls into helpers
    whose effect summary reaches a disk read (``DiskFeatureStore.
    gather_into`` -> ``_read_chunk`` -> memmap slice) are flagged one
    call deep.  Deliberate synchronous reads — the degraded fallback a
    failed stage leaves behind — carry a justified suppression.
    """
    name = "blocking-io-in-epoch-loop"
    code = "GLT014"
    severity = Severity.ERROR
    description = ("synchronous disk read inside an epoch driver's "
                   "batch loop (device idles behind storage; stage "
                   "ahead on the DRAM stager's threads instead)")

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        for scope in module.scopes:
            if not DispatchInEpochLoop._is_epoch_driver(scope.name):
                continue
            mapped = self._mmap_names(module, scope)
            for loop in _walk_own(scope.node):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                for node in ast.walk(loop):
                    f = self._check_node(module, scope, node, mapped,
                                         project)
                    if f is not None:
                        findings.append(f)
        return findings

    @staticmethod
    def _mmap_names(module: ModuleInfo, scope) -> set:
        """Names assigned from mmap constructors anywhere in the scope
        (the constructor is usually hoisted above the loop; the reads
        are the slices inside it)."""
        mapped = set()
        for node in _walk_own(scope.node):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = getattr(node, "value", None)
                if (isinstance(value, ast.Call)
                        and module.call_name(value) in MMAP_CALLS):
                    mapped.update(assign_targets(node))
        return mapped

    def _check_node(self, module: ModuleInfo, scope, node: ast.AST,
                    mapped: set, project) -> Optional[Finding]:
        if (isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name)
                and node.value.id in mapped):
            return self.finding(
                module, node,
                f"slicing memmap '{node.value.id}' inside the batch "
                f"loop of epoch driver '{scope.name}' page-faults to "
                f"storage per batch — stage the rows ahead "
                f"(DramStager.stage_ahead) or justify with a "
                f"suppression")
        if not isinstance(node, ast.Call):
            return None
        name = module.call_name(node)
        if name in DISK_CALLS:
            return self.finding(
                module, node,
                f"'{name}' inside the batch loop of epoch driver "
                f"'{scope.name}' reads storage on the dispatch thread "
                f"every batch — stage ahead on the DRAM stager's "
                f"threads, or justify with a suppression")
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in DISK_READ_METHODS):
            return self.finding(
                module, node,
                f".{node.func.attr}() inside the batch loop of epoch "
                f"driver '{scope.name}' is a synchronous file read per "
                f"batch — move it to a staging thread or justify with "
                f"a suppression")
        # One call deep: a helper whose effect summary reaches a disk
        # read (project-wide pass only).
        if project is not None:
            sym = project.resolve_call(module, scope, node)
            if isinstance(sym, FunctionSymbol):
                summary = project.effects.summary_for(sym)
                if summary.disk:
                    d = summary.disk[0]
                    return self.finding(
                        module, node,
                        f"'{sym.short}' called in the batch loop of "
                        f"epoch driver '{scope.name}' reaches a disk "
                        f"read ({d.detail}, {sym.module.path}:{d.line})"
                        f" — a synchronous storage hit per batch; "
                        f"stage ahead instead")
        return None


@register
class WallClockDuration(Rule):
    """Durations measured by differencing ``time.time()`` readings.

    ``time.time()`` is the WALL clock: NTP slews/steps it, a VM
    migration jumps it, and a leap smear stretches it — a duration
    computed as the difference of two wall readings can come out
    negative or wildly wrong, and these numbers feed SLO histograms
    and retry backoffs.  Durations belong on ``time.monotonic()`` /
    ``time.perf_counter()`` (the convention everywhere in this tree).

    Flagged: a ``-`` expression whose BOTH operands are wall readings —
    direct ``time.time()`` calls or names/attributes assigned from one
    in the same scope.  Subtracting a wall reading from a wall-derived
    *timestamp* (``time.time() - os.path.getmtime(p)``, checkpoint
    mtimes, event ``ts`` fields) is NOT flagged: comparing two wall
    timestamps is what the wall clock is for; only a wall-vs-wall
    *interval* pretends to be a stopwatch.
    """
    name = "wall-clock-duration"
    code = "GLT015"
    severity = Severity.ERROR
    description = ("duration computed from two time.time() readings "
                   "(wall clock steps under NTP/migration; use "
                   "time.monotonic() or time.perf_counter())")

    _WALL = "time.time"

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        for scope in module.scopes:
            wall = self._wall_names(module, scope)
            for node in _walk_own(scope.node):
                if (isinstance(node, ast.BinOp)
                        and isinstance(node.op, ast.Sub)
                        and self._is_wall(module, node.left, wall)
                        and self._is_wall(module, node.right, wall)):
                    findings.append(self.finding(
                        module, node,
                        f"duration from two time.time() readings in "
                        f"'{scope.name}' — the wall clock slews and "
                        f"steps; time a span with time.monotonic() or "
                        f"time.perf_counter(), or justify with a "
                        f"suppression"))
        return findings

    def _wall_names(self, module: ModuleInfo, scope) -> Set[str]:
        """Names / self-attributes assigned from ``time.time()`` in the
        scope (the ``t0 = time.time()`` half of the anti-pattern)."""
        wall: Set[str] = set()
        for node in _walk_own(scope.node):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = getattr(node, "value", None)
                if (isinstance(value, ast.Call)
                        and module.call_name(value) == self._WALL):
                    wall.update(assign_targets(node))
        return wall

    def _is_wall(self, module: ModuleInfo, node: ast.expr,
                 wall: Set[str]) -> bool:
        if (isinstance(node, ast.Call)
                and module.call_name(node) == self._WALL):
            return True
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = _dotted(node)
            return d is not None and d in wall
        return False


# ---------------------------------------------------------------------------
# GLT016 unbalanced-profiler-capture
# ---------------------------------------------------------------------------

@register
class UnbalancedProfilerCapture(Rule):
    """``jax.profiler.start_trace`` without a guaranteed stop.

    A profiler trace left open skews every measurement after it and, on
    TPU, pins the trace buffer until process exit; an exception between
    ``start_trace`` and ``stop_trace`` leaks the capture exactly when
    the run is most worth tracing.  The stop must be UNCONDITIONAL — in
    a ``finally`` block — or the capture should go through the balanced
    context manager :func:`glt_tpu.obs.profiler.capture` (which carries
    the try/finally inside).

    Accepted shapes (both used in this tree):

    * the start inside a ``try`` whose ``finally`` stops, and
    * the start immediately before a ``try`` in the same statement
      list whose ``finally`` stops (the contextmanager idiom:
      ``start_trace(d); try: yield; finally: stop_trace()``).

    ``start_server`` pairs with ``stop_server`` the same way.
    """
    name = "unbalanced-profiler-capture"
    code = "GLT016"
    severity = Severity.ERROR
    description = ("jax.profiler.start_trace/start_server without the "
                   "matching stop in a finally (use try/finally or "
                   "glt_tpu.obs.profiler.capture())")

    _PAIRS = {
        "jax.profiler.start_trace": "jax.profiler.stop_trace",
        "jax.profiler.start_server": "jax.profiler.stop_server",
    }

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        # module.scopes holds only function scopes; a module-level bare
        # start (scripts, __main__ blocks) leaks the same way.
        roots = [(module.tree, "<module>")] + [
            (s.node, s.name) for s in module.scopes]
        for root, scope_name in roots:
            starts: List[ast.Call] = []
            trys: List[ast.Try] = []
            for node in _walk_own(root):
                if (isinstance(node, ast.Call)
                        and module.call_name(node) in self._PAIRS):
                    starts.append(node)
                elif isinstance(node, ast.Try):
                    trys.append(node)
            if not starts:
                continue
            start_ids = {id(n) for n in starts}
            balanced: Set[int] = set()
            # Shape 1: start inside a try whose finally has the stop.
            for t in trys:
                stops = self._final_stops(module, t)
                if not stops:
                    continue
                for part in (t.body, t.handlers, t.orelse):
                    for stmt in part:
                        for n in ast.walk(stmt):
                            if (id(n) in start_ids and
                                    self._PAIRS[module.call_name(n)]
                                    in stops):
                                balanced.add(id(n))
            # Shape 2: start before a try (same statement list) whose
            # finally has the stop — the contextmanager idiom.
            # (walk_own yields children only, so include the root node:
            # its .body is the outermost statement list.)
            for holder in [root, *_walk_own(root)]:
                for field in ("body", "orelse", "finalbody"):
                    stmts = getattr(holder, field, None)
                    if not isinstance(stmts, list):
                        continue
                    self._scan_block(module, stmts, start_ids, balanced)
            for n in starts:
                if id(n) in balanced:
                    continue
                name = module.call_name(n)
                findings.append(self.finding(
                    module, n,
                    f"{name}() in '{scope_name}' without "
                    f"{self._PAIRS[name].split('.')[-1]}() in a finally "
                    f"— an exception leaks the capture; wrap in "
                    f"try/finally or use glt_tpu.obs.profiler.capture()"))
        return findings

    def _final_stops(self, module: ModuleInfo, t: ast.Try) -> Set[str]:
        stops: Set[str] = set()
        for stmt in t.finalbody:
            for n in ast.walk(stmt):
                if isinstance(n, ast.Call):
                    name = module.call_name(n)
                    if name in self._PAIRS.values():
                        stops.add(name)
        return stops

    def _scan_block(self, module: ModuleInfo, stmts: List[ast.stmt],
                    start_ids: Set[int], balanced: Set[int]) -> None:
        for i, stmt in enumerate(stmts):
            pending = [n for n in ast.walk(stmt)
                       if id(n) in start_ids and id(n) not in balanced]
            if not pending:
                continue
            later_stops: Set[str] = set()
            for nxt in stmts[i + 1:]:
                if isinstance(nxt, ast.Try):
                    later_stops |= self._final_stops(module, nxt)
            for n in pending:
                if self._PAIRS[module.call_name(n)] in later_stops:
                    balanced.add(id(n))


# ---------------------------------------------------------------------------
# GLT022 lossy-dtype-narrowing
# ---------------------------------------------------------------------------

@register
class LossyDtypeNarrowing(Rule):
    """Narrowing ``.astype`` casts on feature-path arrays outside the
    codec module.

    Feature compression is centralized in ``glt_tpu/store/quant.py``:
    its codecs carry per-column scale/zero metadata in the store
    manifest and meet a bounded-error contract, and the gather
    epilogues widen back to the logical dtype on-chip.  A bare
    ``x.astype(np.float16)`` / ``.astype(jnp.bfloat16)`` /
    ``.astype("int8")`` elsewhere silently discards precision with no
    metadata to undo it — the error neither shows up in the manifest
    nor in the parity suites that compare the raw and compressed arms.
    Route narrowing through a quant codec (or keep it inside
    ``store/quant.py`` where the contract is tested).
    """
    name = "lossy-dtype-narrowing"
    code = "GLT022"
    severity = Severity.ERROR
    description = ("bare narrowing .astype() on arrays outside "
                   "store/quant.py (precision silently discarded with no "
                   "codec metadata to dequantize)")

    # Sub-f32 floats and sub-i32 ints: casts that drop mantissa or
    # range.  int32 itself stays legal — ids are relabeled into int32
    # range deliberately (GLT004 owns that hazard).
    _NARROW = {
        "numpy.float16", "jax.numpy.float16",
        "jax.numpy.bfloat16", "ml_dtypes.bfloat16",
        "numpy.int8", "jax.numpy.int8",
        "numpy.uint8", "jax.numpy.uint8",
        "numpy.int16", "jax.numpy.int16",
        "numpy.uint16", "jax.numpy.uint16",
        "jax.numpy.float8_e4m3fn", "jax.numpy.float8_e5m2",
        "ml_dtypes.float8_e4m3fn", "ml_dtypes.float8_e5m2",
    }
    _NARROW_STRINGS = {
        "float16", "bfloat16", "int8", "uint8", "int16", "uint16",
        "float8_e4m3fn", "float8_e5m2",
    }
    _EXEMPT_SUFFIX = ("store/quant.py", "store\\quant.py")

    def _narrow_target(self, module: ModuleInfo,
                       arg: ast.expr) -> Optional[str]:
        resolved = module.imports.resolve(arg)
        if resolved in self._NARROW:
            return resolved
        if (isinstance(arg, ast.Constant)
                and arg.value in self._NARROW_STRINGS):
            return str(arg.value)
        # np.dtype("float16") / jnp.dtype(...) wrappers
        if isinstance(arg, ast.Call):
            name = module.call_name(arg) or ""
            if name in ("numpy.dtype", "jax.numpy.dtype") and arg.args:
                return self._narrow_target(module, arg.args[0])
        return None

    def check(self, module: ModuleInfo, project=None
              ) -> List[Finding]:
        path = module.path.replace("\\", "/")
        if path.endswith("store/quant.py") or getattr(
                module, "module_name", "").endswith("store.quant"):
            return []
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                    and node.args):
                continue
            target = self._narrow_target(module, node.args[0])
            if target is None:
                continue
            findings.append(self.finding(
                module, node,
                f"narrowing cast .astype({target}) outside "
                f"store/quant.py: precision is dropped with no codec "
                f"metadata to dequantize — encode through a "
                f"glt_tpu.store.quant codec instead"))
        return findings


# ---------------------------------------------------------------------------
# GLT023 unjittered-retry-loop
# ---------------------------------------------------------------------------

@register
class UnjitteredRetryLoop(Rule):
    """Constant-duration sleep inside a network retry loop.

    A retry loop that catches transport errors and then sleeps a fixed
    constant re-synchronizes every client that failed together: when a
    replica dies, all of its in-flight callers observe the reset within
    milliseconds of each other, all sleep exactly X seconds, and all
    hammer the successor in the same instant — a retry storm that turns
    one failure into rolling overload.  Every retry path in this tree
    (``subgraph_with_retry``, ``RemoteServerConnection``,
    ``FleetRouter`` failover) paces as
    ``min(cap, base * 2**attempt) * (0.5 + 0.5 * rng.random())`` —
    exponential backoff with full-range jitter — so a failed cohort
    decorrelates instead of marching in lockstep.

    Flagged: a ``time.sleep(X)`` or ``<event>.wait(X)`` whose duration
    is a compile-time constant (literals and arithmetic over literals),
    inside a ``while``/``for`` loop that also catches a transport-class
    exception (the ``OSError``/``ConnectionError`` family,
    ``TimeoutError``, ``EOFError``, ``socket.*``, ``*ProtocolError``).
    A duration with any computed component — a name, an attribute, a
    call — is clean: that computation is exactly where backoff and
    jitter live.  Loops that catch only ``Exception`` (heartbeat/poll
    loops pacing themselves, not re-contacting a failed peer) are not
    retry loops and stay clean.
    """
    name = "unjittered-retry-loop"
    code = "GLT023"
    severity = Severity.ERROR
    description = ("constant-duration sleep in a network retry loop "
                   "(failed cohort retries in lockstep — use jittered "
                   "exponential backoff)")

    _NETWORK_EXCS = {
        "OSError", "IOError", "ConnectionError", "ConnectionResetError",
        "ConnectionRefusedError", "ConnectionAbortedError",
        "BrokenPipeError", "TimeoutError", "EOFError",
        "socket.timeout", "socket.error", "socket.gaierror",
        "socket.herror",
    }

    def check(self, module: ModuleInfo, project=None) -> List[Finding]:
        findings: List[Finding] = []
        flagged: Set[int] = set()
        roots = [module.tree] + [s.node for s in module.scopes]
        for root in roots:
            for node in _walk_own(root):
                if not isinstance(node, (ast.While, ast.For)):
                    continue
                if not self._has_network_handler(module, node):
                    continue
                for call in _walk_own(node):
                    if (isinstance(call, ast.Call)
                            and id(call) not in flagged
                            and self._is_const_sleep(module, call)):
                        flagged.add(id(call))
                        findings.append(self.finding(
                            module, call,
                            f"constant sleep in a loop retrying "
                            f"transport errors — every caller that "
                            f"failed together retries together; pace "
                            f"with jittered exponential backoff "
                            f"(min(cap, base * 2**attempt) * random "
                            f"jitter)"))
        return findings

    # -- helpers ----------------------------------------------------------
    def _has_network_handler(self, module: ModuleInfo,
                             loop: ast.AST) -> bool:
        for node in _walk_own(loop):
            if not isinstance(node, ast.ExceptHandler):
                continue
            types = node.type
            if types is None:
                continue    # bare except: a poll loop, not a retry loop
            elts = types.elts if isinstance(types, ast.Tuple) else [types]
            if any(self._is_network_exc(module, e) for e in elts):
                return True
        return False

    def _is_network_exc(self, module: ModuleInfo, expr: ast.expr) -> bool:
        d = _dotted(expr)
        if d is None:
            return False
        resolved = module.imports.resolve(expr) or d
        if d in self._NETWORK_EXCS or resolved in self._NETWORK_EXCS:
            return True
        return d.split(".")[-1].endswith("ProtocolError")

    def _is_const_sleep(self, module: ModuleInfo, call: ast.Call) -> bool:
        if not call.args or call.keywords:
            return False
        name = module.call_name(call)
        is_sleep = name == "time.sleep"
        is_wait = (isinstance(call.func, ast.Attribute)
                   and call.func.attr == "wait")
        if not (is_sleep or is_wait):
            return False
        return self._const_duration(call.args[0])

    def _const_duration(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, (int, float)) \
                and not isinstance(node.value, bool)
        if isinstance(node, ast.UnaryOp):
            return self._const_duration(node.operand)
        if isinstance(node, ast.BinOp):
            return (self._const_duration(node.left)
                    and self._const_duration(node.right))
        return False


def _iter_const_ints(node: ast.expr) -> Iterator[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        yield node.value
    elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for el in node.elts:
            yield from _iter_const_ints(el)


def all_rules() -> List[Rule]:
    return [cls() for cls in RULES.values()]


# The concurrency rules (GLT008/GLT009), the Pallas device-program model
# (GLT017-019, kernelmodel.py), the shard_map collective checks
# (GLT020/021, spmd.py), the wire-protocol verification (GLT024-026,
# protocol.py), and the thread-safety pass (GLT027, threads.py) live in
# their own modules but register into the same RULES table; importing
# here completes the registry for every entry point (cli, tests,
# programmatic use).
from . import concurrency  # noqa: E402,F401  (registration side effect)
from . import kernelmodel  # noqa: E402,F401  (registration side effect)
from . import spmd  # noqa: E402,F401  (registration side effect)
from . import protocol  # noqa: E402,F401  (registration side effect)
from . import threads  # noqa: E402,F401  (registration side effect)
