"""Trace-safety pass: no host syncs inside jit-traced code.

The whole stack's compile-flat guarantee (steady_state_compiles == 0)
rests on traced functions treating runtime tensor
values as opaque: the moment traced code calls `float()`/`int()`/
`bool()`/`len()`/`.item()`/`np.asarray()` on a traced value, branches
on one with `if`/`while`, or formats one into a cache key or metric
label, tracing either fails on an abstract value or — worse — bakes a
runtime value into the program and retraces on every new value. This
pass is the static mirror of the PR 6 retrace-storm flight trigger:
it finds those escapes at lint time instead of ten minutes into a
soak.

Traced scopes are discovered from decoration (`@jax.jit`,
`@functools.partial(jit, ...)`), from call sites (`jax.jit(fn, ...)`
naming a local def), and from the engine's cost registry
(`CostedFunction(fn, ...)`). static_argnums/static_argnames parameters
are host values by contract and seed no taint. The analysis is
intraprocedural: taint seeds at the traced parameters and flows
through assignments, unpacking, arithmetic, subscripts and
`.at[].set()` chains; `.shape`/`.dtype`/`.ndim`/`.size` reads are
static under tracing and drop taint, and branching on a *container*
of traced values (`if adapter:` on a tuple) is a length test — static
— so it is not flagged.

Rules: trace-host-sync, trace-host-branch, trace-format.
"""
from __future__ import annotations

import ast

from .core import Finding, decorator_name, dotted, terminal_name

__all__ = ["run", "traced_functions"]

RULE_SYNC = "trace-host-sync"
RULE_BRANCH = "trace-host-branch"
RULE_FORMAT = "trace-format"

# attribute reads that are static under tracing — they kill taint
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding"}

# builtin coercions that force a device->host sync on a traced value
_SYNC_BUILTINS = {"float", "int", "bool", "len", "str", "complex"}

# numpy module aliases: np.asarray(traced) pulls the value to host
_NUMPY_NAMES = {"np", "numpy", "onp"}

# constructor calls whose *truthiness* is a static length test even
# when the elements are traced (branching on them is fine)
_CONTAINERS = {"tuple", "list", "set", "dict", "frozenset"}

# predicate builtins that inspect python-level structure, never the
# device value — their result is static no matter what they're fed
_STATIC_CALLS = {"isinstance", "issubclass", "hasattr", "callable"}


def _is_jit_ref(node):
    """True for expressions that denote jax.jit: `jit`, `jax.jit`."""
    d = dotted(node)
    return d is not None and (d == "jit" or d.endswith(".jit"))


def _jit_static_params(call):
    """(static_argnums, static_argnames) keyword values of a jit call,
    as python tuples of int/str literals (best effort)."""
    nums, names = (), ()
    for kw in getattr(call, "keywords", ()):
        if kw.arg == "static_argnums":
            nums = _const_tuple(kw.value, int)
        elif kw.arg == "static_argnames":
            names = _const_tuple(kw.value, str)
    return nums, names


def _const_tuple(node, typ):
    if isinstance(node, ast.Constant) and isinstance(node.value, typ):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, typ):
                out.append(e.value)
        return tuple(out)
    return ()


def traced_functions(tree):
    """[(FunctionDef, static_argnums, static_argnames)] for every def
    in `tree` that is jit-traced — by decoration, by a visible
    `jax.jit(name, ...)` / `CostedFunction(name, ...)` call on its
    name, or by being nested inside a traced def (handled later by the
    checker itself)."""
    by_name = {}                      # name -> [FunctionDef]
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            by_name.setdefault(node.name, []).append(node)
    traced = {}                       # id(def) -> (def, nums, names)

    def mark(fn, nums=(), names=()):
        traced.setdefault(id(fn), (fn, tuple(nums), tuple(names)))

    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if _is_jit_ref(dec):
                    mark(node)
                elif isinstance(dec, ast.Call):
                    if _is_jit_ref(dec.func):
                        mark(node, *_jit_static_params(dec))
                    elif (terminal_name(dec.func) == "partial"
                          and dec.args and _is_jit_ref(dec.args[0])):
                        mark(node, *_jit_static_params(dec))
        elif isinstance(node, ast.Call):
            fname = terminal_name(node.func)
            is_jit = _is_jit_ref(node.func)
            # shard_map bodies trace exactly like jit bodies (the
            # serving tp dispatch wraps its program this way before
            # the outer jit), so they get the same discipline
            if not (is_jit or fname in ("CostedFunction", "shard_map",
                                        "shard_map_compat")):
                continue
            if node.args and isinstance(node.args[0], ast.Name):
                for fn in by_name.get(node.args[0].id, ()):
                    mark(fn, *(_jit_static_params(node)
                               if is_jit else ((), ())))
    return list(traced.values())


class _TraceChecker:
    """Intraprocedural taint walk over one traced function."""

    def __init__(self, path, symbol, findings):
        self.path = path
        self.symbol = symbol
        self.findings = findings
        self.taint = set()
        self.containers = set()       # names holding containers of traced

    # -- taint of an expression -------------------------------------------
    def tainted(self, node):
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.taint
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.tainted(node.value)
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) \
                    and node.func.id in _STATIC_CALLS:
                return False
            # a call stays tainted if its receiver or any argument is
            # (jnp ops, .at[].set() chains, method calls on traced)
            if self.tainted(node.func):
                return True
            return any(self.tainted(a) for a in node.args) or any(
                self.tainted(kw.value) for kw in node.keywords)
        if isinstance(node, (ast.Constant, ast.Lambda)):
            return False
        return any(self.tainted(c) for c in ast.iter_child_nodes(node))

    def _branch_static(self, test):
        """True when a tainted test is actually trace-static: a bare
        (possibly negated) container-of-traced name, or an identity
        check against None — `x is None` reads the PYTHON identity of
        the tracer object, never its value, so branching on it is an
        ordinary trace-time mode switch (the mask-optional shard_map
        bodies in parallel/sp.py rely on this)."""
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._branch_static(test.operand)
        if isinstance(test, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops) \
                and any(isinstance(c, ast.Constant) and c.value is None
                        for c in [test.left] + list(test.comparators)):
            return True
        return isinstance(test, ast.Name) and test.id in self.containers

    def _flag(self, rule, node, message):
        self.findings.append(Finding(rule, self.path, node.lineno,
                                     self.symbol, message))

    # -- statement walk ----------------------------------------------------
    def seed(self, fndef, static_nums, static_names):
        args = fndef.args
        ordered = list(args.posonlyargs) + list(args.args)
        for i, a in enumerate(ordered):
            if i in static_nums or a.arg in static_names:
                continue
            if a.arg in ("self", "cls"):
                continue
            self.taint.add(a.arg)
        for a in args.kwonlyargs:
            if a.arg not in static_names:
                self.taint.add(a.arg)
        if args.vararg is not None:
            self.taint.add(args.vararg.arg)
        if args.kwarg is not None:
            self.taint.add(args.kwarg.arg)

    def _bind(self, target, tainted, container=False):
        if isinstance(target, ast.Name):
            if tainted:
                self.taint.add(target.id)
                if container:
                    self.containers.add(target.id)
                else:
                    self.containers.discard(target.id)
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e if not isinstance(e, ast.Starred)
                           else e.value, tainted, container)
        # attribute/subscript stores don't create new taint roots

    def _bind_loop_target(self, target, iter_node):
        """Bind a for/comprehension target from its iterable. Dict
        *keys* are static strings even when the values are traced:
        `for k, v in gh.items()` taints only v; `.keys()` taints
        nothing."""
        if isinstance(iter_node, ast.Call) \
                and isinstance(iter_node.func, ast.Attribute):
            attr = iter_node.func.attr
            if attr == "keys":
                return
            if attr == "items" \
                    and isinstance(target, (ast.Tuple, ast.List)) \
                    and len(target.elts) == 2:
                self._bind(target.elts[1], self.tainted(iter_node))
                return
        if isinstance(iter_node, ast.Call) \
                and isinstance(iter_node.func, ast.Name) \
                and iter_node.func.id == "zip" \
                and not iter_node.keywords \
                and isinstance(target, (ast.Tuple, ast.List)) \
                and len(target.elts) == len(iter_node.args) \
                and not any(isinstance(a, ast.Starred)
                            for a in iter_node.args):
            # `for a, b in zip(xs, ys)` taints each target from ITS
            # OWN iterable — a static multiplier list zipped next to
            # traced params must not smear taint onto the multiplier
            for elt, arg in zip(target.elts, iter_node.args):
                self._bind(elt if not isinstance(elt, ast.Starred)
                           else elt.value, self.tainted(arg))
            return
        self._bind(target, self.tainted(iter_node))

    def _value_is_container(self, value):
        if isinstance(value, (ast.Tuple, ast.List, ast.Set, ast.Dict,
                              ast.ListComp, ast.SetComp, ast.DictComp,
                              ast.GeneratorExp)):
            return True
        if isinstance(value, ast.Call):
            return terminal_name(value.func) in _CONTAINERS
        return False

    def check_body(self, body):
        for stmt in body:
            self.check_stmt(stmt)

    def check_stmt(self, stmt):
        if isinstance(stmt, ast.FunctionDef):
            # a def nested in traced code is traced too: it inherits
            # the enclosing taint and its own params are traced
            inner = _TraceChecker(self.path,
                                  f"{self.symbol}.{stmt.name}",
                                  self.findings)
            inner.taint = set(self.taint)
            inner.containers = set(self.containers)
            inner.seed(stmt, (), ())
            inner.check_body(stmt.body)
            return
        if isinstance(stmt, ast.Assign):
            self.check_expr(stmt.value)
            t = self.tainted(stmt.value)
            c = self._value_is_container(stmt.value)
            for target in stmt.targets:
                if t and isinstance(target, (ast.Tuple, ast.List)) \
                        and isinstance(stmt.value, ast.Call):
                    # `leaves, spec, rebuild = flatten(out)`: a multi-
                    # return helper yields mixed host structure (lists,
                    # treedefs, callables), not bare tracers — tainting
                    # every target drowns the pass in false positives,
                    # so unpacked call results are trusted as host-side
                    continue
                self._bind(target, t, c)
            return
        if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self.check_expr(stmt.value)
            self._bind(stmt.target, self.tainted(stmt.value),
                       self._value_is_container(stmt.value))
            return
        if isinstance(stmt, ast.AugAssign):
            self.check_expr(stmt.value)
            if self.tainted(stmt.value):
                self._bind(stmt.target, True)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            self.check_expr(stmt.test)
            if self.tainted(stmt.test) \
                    and not self._branch_static(stmt.test):
                kind = "if" if isinstance(stmt, ast.If) else "while"
                self._flag(RULE_BRANCH, stmt,
                           f"python `{kind}` on a traced value forces a "
                           f"host sync mid-trace (use jnp.where / "
                           f"lax.cond, or hoist to a static arg)")
            self.check_body(stmt.body)
            self.check_body(stmt.orelse)
            return
        if isinstance(stmt, ast.Assert):
            self.check_expr(stmt.test)
            if self.tainted(stmt.test):
                self._flag(RULE_BRANCH, stmt,
                           "assert on a traced value syncs (use "
                           "checkify or a host-side validation)")
            return
        if isinstance(stmt, ast.For):
            self.check_expr(stmt.iter)
            self._bind_loop_target(stmt.target, stmt.iter)
            self.check_body(stmt.body)
            self.check_body(stmt.orelse)
            return
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                self.check_expr(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars,
                               self.tainted(item.context_expr))
            self.check_body(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self.check_body(stmt.body)
            for h in stmt.handlers:
                self.check_body(h.body)
            self.check_body(stmt.orelse)
            self.check_body(stmt.finalbody)
            return
        if isinstance(stmt, (ast.Return, ast.Expr)):
            if stmt.value is not None:
                self.check_expr(stmt.value)
            return
        if isinstance(stmt, ast.Raise):
            if stmt.exc is not None:
                self.check_expr(stmt.exc)
            return
        # remaining statements: still scan nested expressions
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self.check_expr(child)

    # -- expression checks -------------------------------------------------
    def check_expr(self, node):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self._check_call(sub)
            elif isinstance(sub, ast.IfExp):
                if self.tainted(sub.test) \
                        and not self._branch_static(sub.test):
                    self._flag(RULE_BRANCH, sub,
                               "conditional expression on a traced "
                               "value (use jnp.where)")
            elif isinstance(sub, ast.JoinedStr):
                if any(self.tainted(v.value) for v in sub.values
                       if isinstance(v, ast.FormattedValue)):
                    self._flag(RULE_FORMAT, sub,
                               "f-string interpolates a traced value "
                               "(a cache key or label built from "
                               "runtime tensor data retraces per "
                               "value)")
            elif isinstance(sub, ast.comprehension):
                self._bind_loop_target(sub.target, sub.iter)
                for cond in sub.ifs:
                    if self.tainted(cond):
                        self._flag(RULE_BRANCH, cond,
                                   "comprehension filter on a traced "
                                   "value")

    def _check_call(self, call):
        func = call.func
        if isinstance(func, ast.Name) and func.id in _SYNC_BUILTINS:
            if any(self.tainted(a) for a in call.args):
                self._flag(RULE_SYNC, call,
                           f"`{func.id}()` on a traced value forces a "
                           f"device->host sync inside the trace")
            return
        if isinstance(func, ast.Attribute):
            if func.attr == "item" and self.tainted(func.value):
                self._flag(RULE_SYNC, call,
                           "`.item()` on a traced value syncs inside "
                           "the trace")
                return
            if func.attr in ("asarray", "array") \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id in _NUMPY_NAMES \
                    and any(self.tainted(a) for a in call.args):
                self._flag(RULE_SYNC, call,
                           f"`{func.value.id}.{func.attr}()` on a "
                           f"traced value materializes it on host "
                           f"mid-trace (use jnp)")
                return
            if func.attr == "format" \
                    and (any(self.tainted(a) for a in call.args)
                         or any(self.tainted(kw.value)
                                for kw in call.keywords)):
                self._flag(RULE_FORMAT, call,
                           "`.format()` of a traced value (runtime "
                           "tensor data in a string key/label)")
                return
        d = dotted(func)
        if d in ("jax.device_get", "device_get") \
                and any(self.tainted(a) for a in call.args):
            self._flag(RULE_SYNC, call,
                       "`device_get` inside a traced scope")


def run(ctx):
    findings = []
    for path, tree in ctx.trees.items():
        for fndef, nums, names in traced_functions(tree):
            symbol = fndef.name
            checker = _TraceChecker(path, symbol, findings)
            checker.seed(fndef, nums, names)
            checker.check_body(fndef.body)
    return findings
