"""JAX trace-discipline pass: recompile and host-sync hazards.

Static half
-----------
An interprocedural walk over the jit/scan/shard_map entry points.  Trace
roots are found syntactically — ``jax.jit(f)`` / ``jax.lax.scan(f, …)``
/ ``shard_map(f, …)`` where ``f`` is a local function or lambda, plus
``@jax.jit``-decorated defs — and each root's body (nested defs
included, one transitive hop through same-module functions via the R4
call-graph walker) is checked for the hazards that silently turn a
compiled hot loop into a per-call retrace or a device→host sync stall:

T1  Python-value branching on a traced argument (``if x > 0:`` where
    ``x`` is traced).  Concretises the tracer per call; under jit it
    either fails or forces a recompile per branch arm.  Branching on
    ``.shape``/``.ndim``/``len()``/``is None`` is static and allowed.
T2  Host sync reachable under trace: ``.item()``, ``.tolist()``,
    ``float()``/``int()`` of a traced value, ``np.asarray``/``np.array``
    on a traced value, ``jax.device_get``, ``.block_until_ready()`` —
    and ``tracing.phase(...)``, host timing that would run once, at
    trace time.
T3  Per-call (re)jit: a ``jax.jit(...)`` whose compiled callable cannot
    outlive the call site — invoked immediately (``jax.jit(f)(x)``), or
    built inside a function that neither returns it, stores it on
    ``self``, nor is a factory (``make_*``; module-level jit is fine).
    jit caches per function object, so a fresh closure per call
    re-traces every time (see train/loop.py's LRU factories).
T4  Traced value in a shape position (``jnp.zeros(n)``, ``x.reshape(n)``
    with traced ``n``): shapes must be static under jit; a traced shape
    is a guaranteed ConcretizationTypeError or per-value recompile.

Static args declared via ``static_argnums``/``static_argnames`` are
excluded from the traced set.  Findings use the shared ``Finding`` type
and honour ``# lint-ok: T<n> <reason>`` suppressions.

Runtime half
------------
``RecompileGuard`` counts XLA backend compiles through
``jax.monitoring`` and ``guard_hot_loop`` wraps a hot-loop callable so
that, once a given (callable, abstract-signature) key has run once
(the warm-up trace), any later call under the same key that triggers a
fresh backend compile raises ``RecompileError``.  The pytest plugin
installs it over ``Trainer.fit_compiled``, ``ShardedStreamTrainer
.fit_round`` and ``OnlineLearner._update`` when ``IOTML_TRACECHECK=1``,
failing any test whose warmed loop retraces.
"""

from __future__ import annotations

import ast
import contextlib
import functools
import os
import threading
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .lint import Finding, call_graph_for, suppressions_for
from .program import FileUnit, Program

PASS_RULES: Dict[str, str] = {
    "T1": "Python-value branch on a traced argument inside a trace",
    "T2": "host sync (.item/float/np.asarray/device_get) under trace",
    "T3": "per-call jax.jit: compiled callable cannot outlive the call",
    "T4": "traced value used in a static shape position",
}

#: the jit/scan/shard_map surfaces this pass walks by default,
#: relative to the iotml package root
TRACE_TARGET_RELS: Tuple[str, ...] = (
    "train/loop.py",
    "parallel/streaming.py",
    "parallel/data_parallel.py",
    "core/normalize.py",
    "online/learner.py",
)

#: enclosing-function names allowed to build jit callables without
#: returning/storing them elsewhere (factory idiom; see train/loop.py)
_FACTORY_PREFIXES = ("make", "_make")

_SHAPE_BUILDERS = frozenset({"zeros", "ones", "full", "empty", "arange",
                             "broadcast_to", "eye", "tri"})
_HOST_SYNC_ATTRS = frozenset({"item", "tolist", "block_until_ready"})


def _line_node(line: int):
    import types
    return types.SimpleNamespace(lineno=line, end_lineno=line)


def _is_jax_jit(node: ast.AST) -> bool:
    """``jax.jit`` / bare ``jit`` / ``jax.jit`` inside functools.partial."""
    if isinstance(node, ast.Attribute):
        return node.attr == "jit" and isinstance(node.value, ast.Name) \
            and node.value.id == "jax"
    return isinstance(node, ast.Name) and node.id == "jit"


def _static_params(call: ast.Call, fn_args: ast.arguments) -> Set[str]:
    """Param names excluded from tracing by static_argnums/argnames."""
    out: Set[str] = set()
    names = [a.arg for a in fn_args.posonlyargs + fn_args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and isinstance(sub.value,
                                                                str):
                    out.add(sub.value)
        elif kw.arg == "static_argnums":
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) \
                        and isinstance(sub.value, int) \
                        and 0 <= sub.value < len(names):
                    out.add(names[sub.value])
    return out


class _Root:
    """One trace entry point: the function AST plus its traced params."""

    __slots__ = ("fn", "traced", "line")

    def __init__(self, fn, traced: Set[str], line: int):
        self.fn = fn
        self.traced = traced
        self.line = line


def _param_names(args: ast.arguments) -> List[str]:
    return [a.arg for a in args.posonlyargs + args.args
            if a.arg not in ("self", "cls")]


def _collect_roots(tree: ast.Module,
                   bodies: Dict[str, ast.AST]) -> List[_Root]:
    roots: List[_Root] = []
    seen: Set[int] = set()

    def add(fn, static: Set[str]) -> None:
        if id(fn) in seen:
            return
        seen.add(id(fn))
        traced = set(_param_names(fn.args)) - static
        roots.append(_Root(fn, traced, fn.lineno))

    def resolve(node: ast.AST):
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            body = bodies.get(node.id)
            if isinstance(body, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return body
        return None

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            fn = None
            static: Set[str] = set()
            if _is_jax_jit(node.func):
                fn = resolve(node.args[0])
                if fn is not None:
                    static = _static_params(node, fn.args)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "scan":
                fn = resolve(node.args[0])
            elif (isinstance(node.func, ast.Name)
                  and node.func.id == "shard_map") or \
                    (isinstance(node.func, ast.Attribute)
                     and node.func.attr == "shard_map"):
                fn = resolve(node.args[0])
            if fn is not None:
                add(fn, static)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                call = dec if isinstance(dec, ast.Call) else None
                target = call.func if call else dec
                # @jax.jit and @partial(jax.jit, ...) both trace the def
                if _is_jax_jit(target):
                    add(node, _static_params(call, node.args)
                        if call else set())
                elif call and isinstance(target, ast.Name) \
                        and target.id == "partial" and call.args \
                        and _is_jax_jit(call.args[0]):
                    add(node, _static_params(call, node.args))
    return roots


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _static_wrapped(test: ast.AST, traced: Set[str]) -> Set[str]:
    """Traced names that only appear in STATIC positions of a branch
    test: ``x is None``, ``x.shape``/``x.ndim``/``x.dtype``,
    ``len(x)``/``isinstance(x, …)`` — all resolved at trace time."""
    ok: Set[str] = set()
    for sub in ast.walk(test):
        if isinstance(sub, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in sub.ops):
            ok |= _names_in(sub) & traced
        elif isinstance(sub, ast.Attribute):
            ok |= _names_in(sub.value) & traced
        elif isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                and sub.func.id in ("len", "isinstance", "hasattr",
                                    "getattr", "callable"):
            for a in sub.args:
                ok |= _names_in(a) & traced
    return ok


class _RootChecker:
    """Walks one trace root (nested defs inline, one hop into module
    functions it calls by bare name) and emits T1/T2/T4."""

    def __init__(self, unit: FileUnit, bodies: Dict[str, ast.AST],
                 sup, findings: List[Finding]):
        self.unit = unit
        self.bodies = bodies
        self.sup = sup
        self.findings = findings
        self._visited: Set[int] = set()

    def emit(self, rule: str, line: int, message: str) -> None:
        if self.sup is not None \
                and self.sup.suppressed(rule, _line_node(line)):
            return
        self.findings.append(
            Finding(self.unit.path, line, rule, message))

    def check(self, root: _Root) -> None:
        self._body(root.fn, root.traced, depth=0)

    def _body(self, fn, traced: Set[str], depth: int) -> None:
        if id(fn) in self._visited or depth > 2:
            return
        self._visited.add(id(fn))
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                hot = (_names_in(node.test) & traced) \
                    - _static_wrapped(node.test, traced)
                for name in sorted(hot):
                    self.emit(
                        "T1", node.lineno,
                        f"branch on traced value {name!r} inside a "
                        f"traced function: concretises per call "
                        f"(use jnp.where / lax.cond, or mark it "
                        f"static)")
            elif isinstance(node, ast.Call):
                self._call(node, traced, depth)

    def _call(self, node: ast.Call, traced: Set[str], depth: int) -> None:
        func = node.func
        # T2: host syncs
        if isinstance(func, ast.Attribute):
            if func.attr in _HOST_SYNC_ATTRS:
                self.emit(
                    "T2", node.lineno,
                    f".{func.attr}() under trace forces a device→host "
                    f"sync (move it outside the jitted function)")
                return
            if func.attr in ("asarray", "array") \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id in ("np", "numpy", "onp") \
                    and node.args and _names_in(node.args[0]) & traced:
                self.emit(
                    "T2", node.lineno,
                    f"np.{func.attr}() on traced value under trace "
                    f"pulls the array to host (use jnp)")
                return
            if func.attr == "device_get":
                self.emit(
                    "T2", node.lineno,
                    "jax.device_get under trace is a host sync")
                return
            if func.attr == "phase" and isinstance(func.value, ast.Name) \
                    and func.value.id == "tracing":
                self.emit(
                    "T2", node.lineno,
                    "tracing.phase() under trace times the TRACING of "
                    "the function, once, and nothing of its runs: open "
                    "phases on the host, around the jitted call")
                return
            # T4: traced value in a shape position.  Names that only
            # appear under an attribute access (x.shape, x.ndim) or a
            # len() are static and fine.
            if func.attr in _SHAPE_BUILDERS and node.args:
                hot = (_names_in(node.args[0]) & traced) \
                    - _static_wrapped(node.args[0], traced)
                if hot:
                    self.emit(
                        "T4", node.lineno,
                        f"traced value {sorted(hot)[0]!r} in the shape "
                        f"argument of {func.attr}(): shapes must be "
                        f"static under jit")
                    return
            if func.attr == "reshape":
                hot = set()
                for a in node.args:
                    hot |= (_names_in(a) & traced) \
                        - _static_wrapped(a, traced)
                if hot:
                    self.emit(
                        "T4", node.lineno,
                        f"traced value {sorted(hot)[0]!r} in reshape() "
                        f"target shape: shapes must be static under "
                        f"jit")
                    return
        elif isinstance(func, ast.Name):
            if func.id in ("float", "int", "bool") and node.args \
                    and _names_in(node.args[0]) & traced:
                names = sorted(_names_in(node.args[0]) & traced)
                self.emit(
                    "T2", node.lineno,
                    f"{func.id}() of traced value {names[0]!r} under "
                    f"trace is a host sync (keep it on device)")
                return
            # one transitive hop: a bare-name call into a same-module
            # function traces that function's body too — its params
            # bound to our traced args become traced
            body = self.bodies.get(func.id)
            if isinstance(body, (ast.FunctionDef, ast.AsyncFunctionDef)):
                params = _param_names(body.args)
                passed: Set[str] = set()
                for i, a in enumerate(node.args):
                    if i < len(params) and _names_in(a) & traced:
                        passed.add(params[i])
                if passed:
                    self._body(body, passed, depth + 1)


def _check_t3(unit: FileUnit, sup, findings: List[Finding]) -> None:
    """Per-call jit: flag jax.jit calls whose compiled callable cannot
    outlive the call site."""
    tree = unit.tree

    def emit(line: int, message: str) -> None:
        if sup is not None and sup.suppressed("T3", _line_node(line)):
            return
        findings.append(Finding(unit.path, line, "T3", message))

    # map each jit Call to its innermost enclosing function
    encl: Dict[int, ast.AST] = {}

    def index(node: ast.AST, fn) -> None:
        for child in ast.iter_child_nodes(node):
            here = child if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef,
                        ast.Lambda)) else fn
            encl[id(child)] = fn
            index(child, here)

    index(tree, None)

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_jax_jit(node.func)):
            continue
        fn = encl.get(id(node))
        parent = _parent_of(tree, node)
        # jax.jit(f)(x): traced fresh every call, compiled program
        # dropped on the floor
        if isinstance(parent, ast.Call) and parent.func is node:
            emit(node.lineno,
                 "jax.jit(...)(...) invoked immediately: re-traces "
                 "every call — build the jitted callable once (module "
                 "level, factory, or LRU cache)")
            continue
        if fn is None:
            continue  # module level: compiled once per process
        name = getattr(fn, "name", "<lambda>")
        if name == "make" or any(name.startswith(p)
                                 for p in _FACTORY_PREFIXES):
            continue
        if isinstance(parent, ast.Return):
            continue  # returned: the caller owns its lifetime
        if isinstance(parent, ast.Assign) and any(
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name) and t.value.id == "self"
                for t in parent.targets):
            continue  # stored on the instance: compiled once per object
        emit(node.lineno,
             f"jax.jit built inside {name!r} neither returned, stored "
             f"on self, nor in a make_* factory: a fresh closure per "
             f"call re-traces every time")


_PARENTS: Dict[int, Dict[int, ast.AST]] = {}


def _parent_of(tree: ast.Module, node: ast.AST) -> Optional[ast.AST]:
    table = _PARENTS.get(id(tree))
    if table is None:
        table = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                table[id(child)] = parent
        _PARENTS[id(tree)] = table
    return table.get(id(node))


def check_file(unit: FileUnit) -> List[Finding]:
    """All T-rules over one file; shares the unit's parse + call graph."""
    if unit.tree is None:
        e = unit.parse_error
        return [Finding(unit.path, (e.lineno or 0) if e else 0, "PARSE",
                        f"syntax error: {e.msg if e else 'unparseable'}")]
    findings: List[Finding] = []
    sup = suppressions_for(unit)
    graph = call_graph_for(unit)
    bodies = graph.bodies if graph is not None else {}
    roots = unit.cached("traceroots",
                        lambda u: _collect_roots(u.tree, bodies))
    checker = _RootChecker(unit, bodies, sup, findings)
    for root in roots:
        checker.check(root)
    _check_t3(unit, sup, findings)
    _PARENTS.pop(id(unit.tree), None)
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def analyze(root: Optional[str] = None, *,
            paths: Optional[Iterable[str]] = None,
            program: Optional[Program] = None) -> List[Finding]:
    """Run the static trace-discipline pass.

    Default scope is the known jit/scan/shard_map surfaces
    (``TRACE_TARGET_RELS``) under the package root; pass ``paths`` to
    check arbitrary files (fixtures, new modules)."""
    from .lint import default_root
    program = program if program is not None else Program()
    findings: List[Finding] = []
    if paths is not None:
        for unit in program.units(paths):
            findings.extend(check_file(unit))
    else:
        base = root if root is not None else default_root()
        for rel in TRACE_TARGET_RELS:
            p = os.path.join(base, rel)
            if os.path.exists(p):
                findings.extend(check_file(program.unit(p, rel=rel)))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


# --------------------------------------------------------------------------
# runtime half: the recompile guard
# --------------------------------------------------------------------------

class RecompileError(AssertionError):
    """A warmed hot loop triggered a fresh XLA backend compile."""


class RecompileGuard:
    """Process-wide backend-compile counter fed by jax.monitoring.

    ``install()`` registers one event-duration listener (idempotent);
    ``compiles()`` is the count so far.  jax has no unregister API, so
    the listener stays for the process lifetime — it only bumps an int.
    """

    _lock = threading.Lock()
    _installed = False
    _compiles = 0
    #: the jax-internal event key for a real XLA backend compile
    _EVENT = "/jax/core/compile/backend_compile_duration"

    @classmethod
    def install(cls) -> None:
        with cls._lock:
            if cls._installed:
                return
            import jax.monitoring

            def on_event(event: str, duration: float, **kw) -> None:
                if event == cls._EVENT:
                    with cls._lock:
                        cls._compiles += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._installed = True

    @classmethod
    def compiles(cls) -> int:
        with cls._lock:
            return cls._compiles


@contextlib.contextmanager
def expect_no_recompile(label: str = "hot loop"):
    """Assert the enclosed block triggers zero backend compiles."""
    RecompileGuard.install()
    before = RecompileGuard.compiles()
    yield
    grew = RecompileGuard.compiles() - before
    if grew:
        raise RecompileError(
            f"{label}: {grew} backend compile(s) inside a block "
            f"expected to be warm")


#: (id(self), label, abstract signature) -> warmed; cleared per test by
#: the pytest plugin so id() reuse across tests cannot alias
_WARMED: Set[tuple] = set()


def reset_warm() -> None:
    _WARMED.clear()


def _abstract_sig(args, kwargs) -> tuple:
    """Shape/dtype signature: two calls with the same signature must
    reuse the compiled program, so a compile on the second is a
    retrace."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
    out = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            out.append((tuple(leaf.shape), str(leaf.dtype)))
        elif isinstance(leaf, (int, float, bool, str, bytes,
                               type(None))):
            # jit treats python scalars as weak-typed values of one
            # abstract type; only static args key on the VALUE, and
            # those change the signature legitimately
            out.append((type(leaf).__name__, leaf
                        if isinstance(leaf, (int, str, bool)) else None))
        else:
            out.append(type(leaf).__name__)
    return tuple(out)


def guard_hot_loop(fn, label: Optional[str] = None):
    """Wrap a hot-loop method: first call per (instance, signature) is
    the warm-up trace; any later same-signature call that triggers a
    backend compile raises RecompileError (fails the test)."""
    RecompileGuard.install()
    tag = label or getattr(fn, "__qualname__", getattr(fn, "__name__",
                                                       "hot-loop"))

    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        key = (id(self), tag, _abstract_sig(args, kwargs))
        before = RecompileGuard.compiles()
        out = fn(self, *args, **kwargs)
        if key in _WARMED and RecompileGuard.compiles() > before:
            raise RecompileError(
                f"{tag}: warmed hot loop re-traced (backend compile "
                f"after the warm-up call with an identical "
                f"shape/dtype signature)")
        _WARMED.add(key)
        return out

    wrapped.__iotml_traceguard__ = True
    wrapped.__wrapped__ = fn
    return wrapped


#: the hot loops the pytest plugin guards under IOTML_TRACECHECK=1
_GUARD_TARGETS = (
    ("iotml.train.loop", "Trainer", "fit_compiled"),
    ("iotml.parallel.streaming", "ShardedStreamTrainer", "fit_round"),
    ("iotml.parallel.streaming", "ShardedStreamTrainer", "fit_compiled"),
    ("iotml.online.learner", "OnlineLearner", "_update"),
)


def install_runtime_guard() -> List[str]:
    """Patch the known hot loops with guard_hot_loop (idempotent).
    Returns the list of patched qualnames (for the plugin's report)."""
    import importlib

    patched: List[str] = []
    for mod_name, cls_name, meth in _GUARD_TARGETS:
        try:
            mod = importlib.import_module(mod_name)
            cls = getattr(mod, cls_name)
            fn = cls.__dict__.get(meth)
        except Exception:
            continue
        if fn is None or getattr(fn, "__iotml_traceguard__", False):
            continue
        setattr(cls, meth, guard_hot_loop(fn, f"{cls_name}.{meth}"))
        patched.append(f"{cls_name}.{meth}")
    return patched
