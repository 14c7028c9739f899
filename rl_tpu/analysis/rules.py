"""rlint rules R001–R004 (R005 lives in lockorder.py).

R001 host-sync-in-hot-path — ``.item()``, ``float()/int()/bool()`` on a
    non-literal, ``np.asarray``/``np.array``, ``jax.device_get``,
    ``.block_until_ready()`` inside any function reachable from a hot
    root (jit/lax body or ``@hot_path`` host loop). Each of these forces
    the host to wait on the device (or copies device→host), which stalls
    the dispatch pipeline — the exact regression PR 1 and PR 4 each
    removed by hand.

R002 donation-after-use — an argument passed through a
    ``donate_argnums``/``donate_argnames`` dispatch is dead: XLA may
    reuse its buffer for the outputs. Referencing it afterwards in the
    same scope (or re-passing it on the next loop iteration without
    rebinding) reads freed memory — PR 5 fixed a real heap corruption
    from exactly this. A donating dispatch is a ``jax.jit`` wrapper or a
    program a registry ``register(..., donate_argnums=...)``-ed, also one
    handed out by a getter (the serving engine's ``_get_*_prog``); a use
    in the other arm of the ``if`` that holds the call is not "after".

R003 PRNG key reuse — the same key consumed by two randomness calls
    (or split twice) without an intervening rebind silently correlates
    samples.

R004 recompile hazards — tracer-dependent Python branches inside traced
    roots (``if`` on a non-static parameter retraces or crashes), and
    ``jax.jit`` calls constructed inside a loop (a fresh jit wrapper per
    iteration defeats the compile cache).

R006 registry bypass — a literal ``jax.jit``/``jax.pjit`` (call or
    decorator) inside ``rl_tpu/models/`` or ``rl_tpu/trainers/``. Hot
    programs in those packages are expected to go through
    :class:`rl_tpu.compile.ProgramRegistry`: a raw jit wrapper is
    invisible to ``aot_warmup()``, the persistent executable store, and
    the per-program compile metrics, so it silently re-pays the
    cold-start tax this subsystem exists to kill. Intentional raw sites
    (docstring examples, cold-path eval helpers) live in the baseline
    with a reason.

R007 cross-thread shared-state hazard — a ``self.X`` field rebound inside
    a function reachable from a ``Supervisor.spawn``/``threading.Thread``
    target and read from a method running on other threads, with neither
    side inside a ``with <lock>`` (lock identity reuses the R005
    lock-site index). GIL-atomic flag reads that are *intentionally*
    lock-free live in the baseline with a reason.
"""

from __future__ import annotations

import ast

from .core import FunctionInfo, ModuleIndex, PackageIndex, canon, _target_names
from .findings import Finding

__all__ = ["run_rules"]

_HOST_SYNC_CASTS = {"float", "int", "bool"}
_HOST_SYNC_CALLS = {
    "numpy.asarray": "np.asarray",
    "numpy.array": "np.array",
    "jax.device_get": "jax.device_get",
}
_RANDOM_SAFE = {
    "PRNGKey", "key", "key_data", "wrap_key_data", "fold_in", "clone",
    "key_impl", "default_prng_impl",
}
_JIT_NAMES = {"jax.jit", "jax.pjit"}


def _iter_functions(m: ModuleIndex):
    return m.functions.values()


def _body_nodes(fn: FunctionInfo):
    """Walk a function body without descending into nested defs/lambdas
    (those are separate FunctionInfos / out of scope)."""
    stack = list(ast.iter_child_nodes(fn.node))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


# -- R001 ---------------------------------------------------------------------

def _r001(index: PackageIndex, m: ModuleIndex) -> list[Finding]:
    out: list[Finding] = []
    for fn in _iter_functions(m):
        if not index.is_hot(fn.qualname):
            continue
        why = index.hot_from.get(fn.qualname, "hot")
        for node in _body_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            label = None
            if isinstance(node.func, ast.Attribute):
                if node.func.attr == "item" and not node.args:
                    label = ".item()"
                elif node.func.attr == "block_until_ready":
                    label = ".block_until_ready()"
            name = canon(node.func, m.aliases)
            if label is None and name in _HOST_SYNC_CASTS:
                if node.args and not isinstance(node.args[0], ast.Constant):
                    label = f"{name}()"
            if label is None and name in _HOST_SYNC_CALLS:
                label = _HOST_SYNC_CALLS[name]
            if label is not None:
                out.append(Finding(
                    rule="R001", file=m.path, line=node.lineno,
                    qualname=fn.display, snippet=m.snippet(node),
                    message=f"host sync {label} in hot path ({why})",
                ))
    return out


# -- R002 ---------------------------------------------------------------------

def _donated_positions(call: ast.Call) -> tuple[tuple, tuple] | None:
    """(argnums, argnames) literally present in a jit call's donate kwargs;
    None when the call donates nothing."""
    nums: list[int] = []
    names: list[str] = []
    seen = False
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            seen = True
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.append(n.value)
        elif kw.arg == "donate_argnames":
            seen = True
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.append(n.value)
    if not seen or not (nums or names):
        return None
    return tuple(sorted(set(nums))), tuple(names)


def _is_program_factory(call: ast.Call, m: ModuleIndex) -> bool:
    """``jax.jit(...)`` or a program registry's ``<x>.register(...)``
    (``ProgramRegistry.register`` hands its keywords to ``jax.jit``)."""
    if canon(call.func, m.aliases) in _JIT_NAMES:
        return True
    return isinstance(call.func, ast.Attribute) and call.func.attr == "register"


def _scoped_nodes(m: ModuleIndex):
    """``(scope, node)`` over the module: None for nodes outside every
    function body (class bodies included), else the qualname of the
    function whose body holds the node (nested defs are their own)."""
    stack = list(m.tree.body)
    while stack:
        node = stack.pop()
        yield None, node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))
    for fn in _iter_functions(m):
        for node in _body_nodes(fn):
            yield fn.qualname, node


def _collect_donating_callables(m: ModuleIndex) -> tuple[dict, dict]:
    """``(donors, getters)``. ``donors`` maps ``(scope, name)`` to donated
    (argnums, argnames): assignments of ``jit(...)`` / ``register(...)``
    results and @partial(jax.jit, donate_*) decorators; a plain name
    bound inside a function is that function's (scope = its qualname),
    ``self.x``, module-level names and decorated defs are the module's
    (scope None). ``getters`` maps the call names of functions that
    RETURN such a program (``self._get_decode_prog``) to its donation:
    what they hand out is tracked one assignment further, and a direct
    ``self._get_cow_prog(n)(pools, ...)`` is a donating call."""
    donors: dict[tuple, tuple] = {}
    getters: dict[str, tuple] = {}

    def bind(scope, targets, pos):
        for t in targets:
            for tn in _target_names(t):
                donors[(scope if "." not in tn else None, tn)] = pos

    def made(value):
        if isinstance(value, ast.Call) and _is_program_factory(value, m):
            return _donated_positions(value)
        return None

    for scope, node in _scoped_nodes(m):
        if isinstance(node, ast.Assign):
            pos = made(node.value)
            if pos is not None:
                bind(scope, node.targets, pos)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            pos = _decorated_donation(node, m)
            if pos is not None:
                donors[(None, node.name)] = donors[(None, f"self.{node.name}")] = pos
    for fn in _iter_functions(m):
        name = getattr(fn.node, "name", None)
        for node in _body_nodes(fn) if name else ():
            if isinstance(node, ast.Return) and node.value is not None:
                pos = made(node.value) or donors.get((fn.qualname, _expr_name(node.value)))
                if pos is not None:
                    getters[name] = getters[f"self.{name}"] = pos
    for scope, node in _scoped_nodes(m):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            pos = getters.get(_callee_key(node.value))
            if pos is not None:
                bind(scope, node.targets, pos)
    return donors, getters


def _decorated_donation(node: ast.AST, m: ModuleIndex) -> tuple | None:
    for dec in node.decorator_list:
        if isinstance(dec, ast.Call):
            cname = canon(dec.func, m.aliases)
            is_jit = cname in _JIT_NAMES
            is_partial_jit = (
                cname in {"functools.partial", "partial"}
                and dec.args
                and canon(dec.args[0], m.aliases) in _JIT_NAMES
            )
            if is_jit or is_partial_jit:
                pos = _donated_positions(dec)
                if pos is not None:
                    return pos
    return None


def _expr_name(node: ast.AST) -> str | None:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        return f"{node.value.id}.{node.attr}"
    return None


def _callee_key(node: ast.Call) -> str | None:
    return _expr_name(node.func)


def _assign_lines(fn: FunctionInfo, name: str) -> list[int]:
    lines = []
    for node in _body_nodes(fn):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For, ast.AsyncFor)):
            targets = [node.target]
        elif isinstance(node, ast.withitem) and node.optional_vars is not None:
            targets = [node.optional_vars]
        for t in targets:
            if name in _target_names(t):
                lines.append(t.lineno)
    return lines


def _loads_after(fn: FunctionInfo, name: str, after_line: int) -> list[ast.AST]:
    out = []
    for node in _body_nodes(fn):
        if isinstance(node, (ast.Name, ast.Attribute)) and _expr_name(node) == name:
            if isinstance(getattr(node, "ctx", None), ast.Load) and node.lineno > after_line:
                out.append(node)
    return out


def _exclusive_branches(fn: FunctionInfo, a: int, b: int) -> bool:
    """Lines ``a`` and ``b`` lie in different arms of one ``if``: control
    that ran the one never runs the other."""
    def span(stmts):
        return (stmts[0].lineno, stmts[-1].end_lineno or stmts[-1].lineno) if stmts else (0, -1)

    for node in _body_nodes(fn):
        if isinstance(node, ast.If):
            (b0, b1), (e0, e1) = span(node.body), span(node.orelse)
            if (b0 <= a <= b1 and e0 <= b <= e1) or (e0 <= a <= e1 and b0 <= b <= b1):
                return True
    return False


def _enclosing_loops(fn: FunctionInfo, line: int) -> list[ast.AST]:
    loops = []
    for node in ast.walk(fn.node):
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            if node.lineno <= line <= (node.end_lineno or node.lineno):
                loops.append(node)
    return loops


def donating_calls(m: ModuleIndex):
    """Every call of a donating program R002 sees in ``m``, as
    ``(function, call node, callee, (argnums, argnames))``: what the rule
    then checks, and what a test reads to know a clean result is not an
    empty one."""
    donors, getters = _collect_donating_callables(m)
    if not donors:
        return
    for fn in _iter_functions(m):
        for node in _body_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            key = _callee_key(node)
            pos = donors.get((fn.qualname, key), donors.get((None, key)))
            if pos is None and isinstance(node.func, ast.Call):
                key = _callee_key(node.func)  # self._get_prog(n)(pools, ...)
                pos = getters.get(key)
            if pos is not None:
                yield fn, node, key, pos


def _r002(index: PackageIndex, m: ModuleIndex) -> list[Finding]:
    out: list[Finding] = []
    for fn, node, key, (nums, names) in donating_calls(m):
        donated_args: list[tuple[str, ast.AST]] = []
        for p in nums:
            if p < len(node.args):
                nm = _expr_name(node.args[p])
                if nm is not None:
                    donated_args.append((nm, node.args[p]))
        for kw in node.keywords:
            if kw.arg in names:
                nm = _expr_name(kw.value)
                if nm is not None:
                    donated_args.append((nm, kw.value))
        call_end = node.end_lineno or node.lineno
        for nm, _arg in donated_args:
            assigns = _assign_lines(fn, nm)
            # straight-line use after the donating call
            for use in _loads_after(fn, nm, call_end):
                killed = any(node.lineno <= a <= use.lineno for a in assigns)
                if not killed and not _exclusive_branches(fn, node.lineno, use.lineno):
                    out.append(Finding(
                        rule="R002", file=m.path, line=use.lineno,
                        qualname=fn.display, snippet=m.snippet(use),
                        message=(
                            f"'{nm}' used after being donated to {key} "
                            f"(donate_argnums={nums or names}) at line {node.lineno}"
                        ),
                    ))
                    break  # one finding per (call, arg)
            else:
                # loop-carried: donated every iteration, never rebound
                for loop in _enclosing_loops(fn, node.lineno):
                    lo, hi = loop.lineno, loop.end_lineno or loop.lineno
                    if not any(lo <= a <= hi for a in assigns):
                        out.append(Finding(
                            rule="R002", file=m.path, line=node.lineno,
                            qualname=fn.display, snippet=m.snippet(node),
                            message=(
                                f"'{nm}' donated to {key} inside a loop without "
                                "rebinding — second iteration passes a freed buffer"
                            ),
                        ))
                        break
    return out


# -- R003 ---------------------------------------------------------------------

def _terminates(stmts: list) -> bool:
    """True when a statement list cannot fall through to the next one."""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break)
    )


class _KeyFlow:
    """Sequential consumed-key tracking over one function body."""

    def __init__(self, m: ModuleIndex, fn: FunctionInfo):
        self.m = m
        self.fn = fn
        self.findings: list[Finding] = []

    def run(self) -> list[Finding]:
        self._block(self.fn.node.body, {})
        return self.findings

    # consumed: name -> (line, callname)
    def _block(self, stmts, consumed: dict) -> dict:
        for st in stmts:
            consumed = self._stmt(st, consumed)
        return consumed

    def _stmt(self, st, consumed: dict) -> dict:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return consumed
        if isinstance(st, ast.If):
            self._expr(st.test, consumed)
            a = self._block(st.body, dict(consumed))
            b = self._block(st.orelse, dict(consumed))
            # a branch that cannot fall through (return/raise/...) does not
            # contribute its consumed-set to the merge — `if p: return rand(k)`
            # leaves k fresh on the fall-through path
            if _terminates(st.body):
                a = dict(consumed)
            if st.orelse and _terminates(st.orelse):
                b = dict(consumed)
            return {**a, **b}
        if isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
            if isinstance(st, (ast.For, ast.AsyncFor)):
                self._expr(st.iter, consumed)
                pre = dict(consumed)
                for nm in _target_names(st.target):
                    pre.pop(nm, None)
            else:
                self._expr(st.test, consumed)
                pre = dict(consumed)
            body_out = self._block(st.body, dict(pre))
            self._check_loop_carry(st, pre, body_out)
            merged = {**consumed, **body_out}
            return self._block(st.orelse, merged)
        if isinstance(st, (ast.With, ast.AsyncWith)):
            for item in st.items:
                self._expr(item.context_expr, consumed)
            return self._block(st.body, consumed)
        if isinstance(st, ast.Try):
            out = self._block(st.body, consumed)
            for h in st.handlers:
                out = {**out, **self._block(h.body, dict(consumed))}
            out = self._block(st.orelse, out)
            return self._block(st.finalbody, out)
        # plain statement: evaluate value first, then apply target kills
        targets: list[ast.AST] = []
        if isinstance(st, ast.Assign):
            self._expr(st.value, consumed)
            targets = st.targets
        elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
            if getattr(st, "value", None) is not None:
                self._expr(st.value, consumed)
            targets = [st.target]
        else:
            for node in ast.iter_child_nodes(st):
                self._expr(node, consumed)
        for t in targets:
            for nm in _target_names(t):
                consumed.pop(nm, None)
        return consumed

    def _check_loop_carry(self, loop, pre: dict, body_out: dict) -> None:
        assigned: set = set()
        for node in ast.walk(loop):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    assigned.update(_target_names(t))
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For, ast.AsyncFor)):
                assigned.update(_target_names(node.target))
        for nm, (line, callname) in body_out.items():
            if nm not in pre and nm not in assigned:
                self.findings.append(Finding(
                    rule="R003", file=self.m.path, line=line,
                    qualname=self.fn.display,
                    snippet=self.m.lines[line - 1].strip() if line <= len(self.m.lines) else "",
                    message=(
                        f"PRNG key '{nm}' consumed by {callname} every loop "
                        "iteration without an intervening split/rebind"
                    ),
                ))

    def _expr(self, node, consumed: dict) -> None:
        if node is None or isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        for call in [n for n in ast.walk(node)
                     if isinstance(n, ast.Call)
                     and not isinstance(n.func, ast.Lambda)]:
            name = canon(call.func, self.m.aliases)
            if not name or not name.startswith("jax.random."):
                continue
            leaf = name.rsplit(".", 1)[-1]
            if leaf in _RANDOM_SAFE:
                continue
            keyarg = call.args[0] if call.args else None
            if keyarg is None:
                for kw in call.keywords:
                    if kw.arg == "key":
                        keyarg = kw.value
            nm = _expr_name(keyarg) if keyarg is not None else None
            if nm is None:
                continue
            if nm in consumed:
                line0, prev = consumed[nm]
                self.findings.append(Finding(
                    rule="R003", file=self.m.path, line=call.lineno,
                    qualname=self.fn.display, snippet=self.m.snippet(call),
                    message=(
                        f"PRNG key '{nm}' reused by jax.random.{leaf} "
                        f"(already consumed by {prev} at line {line0})"
                    ),
                ))
            else:
                consumed[nm] = (call.lineno, f"jax.random.{leaf}")


def _r003(index: PackageIndex, m: ModuleIndex) -> list[Finding]:
    out: list[Finding] = []
    for fn in _iter_functions(m):
        out.extend(_KeyFlow(m, fn).run())
    return out


# -- R004 ---------------------------------------------------------------------

class _DynamicTestVisitor(ast.NodeVisitor):
    """Collect Names in a branch test that read a traced parameter's
    *value* (as opposed to static metadata like .shape/.dtype or
    identity tests like ``x is None``)."""

    _STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

    def __init__(self, params: set):
        self.params = params
        self.hits: list[ast.Name] = []

    def visit_Attribute(self, node):
        if node.attr in self._STATIC_ATTRS:
            return  # x.shape et al. are static under trace
        self.generic_visit(node)

    def visit_Compare(self, node):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return  # `x is None` is a static structure test
        self.generic_visit(node)

    def visit_Call(self, node):
        name = node.func.id if isinstance(node.func, ast.Name) else None
        if name in {"isinstance", "len", "hasattr", "getattr", "callable"}:
            return
        self.generic_visit(node)

    def visit_Name(self, node):
        if node.id in self.params:
            self.hits.append(node)


def _r004(index: PackageIndex, m: ModuleIndex) -> list[Finding]:
    out: list[Finding] = []
    for fn in _iter_functions(m):
        info = index.functions.get(fn.qualname)
        # tracer-dependent Python branches: only in traced roots
        if info is not None and info.is_traced_root:
            dyn = set(info.params) - info.static_params - {"self", "cls"}
            for node in _body_nodes(fn):
                if isinstance(node, (ast.If, ast.While)):
                    v = _DynamicTestVisitor(dyn)
                    v.visit(node.test)
                    if v.hits:
                        names = sorted({h.id for h in v.hits})
                        out.append(Finding(
                            rule="R004", file=m.path, line=node.lineno,
                            qualname=fn.display, snippet=m.snippet(node),
                            message=(
                                f"Python branch on traced argument(s) {names} in "
                                f"{info.hot_detail or 'jit'} body — retraces per value "
                                "or raises ConcretizationTypeError"
                            ),
                        ))
        # jit constructed inside a loop: anywhere
        seen_calls: set = set()
        for node in _body_nodes(fn):
            if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Call) and id(sub) not in seen_calls
                            and canon(sub.func, m.aliases) in _JIT_NAMES):
                        seen_calls.add(id(sub))
                        out.append(Finding(
                            rule="R004", file=m.path, line=sub.lineno,
                            qualname=fn.display, snippet=m.snippet(sub),
                            message=(
                                "jax.jit constructed inside a loop — a fresh wrapper "
                                "per iteration defeats the trace cache"
                            ),
                        ))
    return out


# -- R006 ---------------------------------------------------------------------

# the packages whose hot programs must dispatch through the ProgramRegistry
# (rl_tpu/compile/); matched against the module's repo-relative path
_R006_SCOPE = ("rl_tpu/models/", "rl_tpu/trainers/")


def _r006(index: PackageIndex, m: ModuleIndex) -> list[Finding]:
    if not any(seg in m.path for seg in _R006_SCOPE):
        return []
    out: list[Finding] = []
    seen: set = set()

    def add(node, display: str, label: str) -> None:
        if id(node) in seen:
            return
        seen.add(id(node))
        out.append(Finding(
            rule="R006", file=m.path, line=node.lineno,
            qualname=display, snippet=m.snippet(node),
            message=(
                f"{label} bypasses the ProgramRegistry — the executable is "
                "invisible to aot_warmup(), the persistent store, and the "
                "compile metrics; register it via "
                "rl_tpu.compile.get_program_registry().register(...)"
            ),
        ))

    for fn in _iter_functions(m):
        for dec in fn.node.decorator_list:
            if isinstance(dec, ast.Call):
                cname = canon(dec.func, m.aliases)
                if cname in _JIT_NAMES:
                    add(dec, fn.display, f"@{cname}(...) decorator")
                elif (cname in {"functools.partial", "partial"} and dec.args
                        and canon(dec.args[0], m.aliases) in _JIT_NAMES):
                    add(dec, fn.display, "@partial(jax.jit, ...) decorator")
            else:
                cname = canon(dec, m.aliases)
                if cname in _JIT_NAMES:
                    add(dec, fn.display, f"@{cname} decorator")
        for node in _body_nodes(fn):
            if (isinstance(node, ast.Call)
                    and canon(node.func, m.aliases) in _JIT_NAMES):
                add(node, fn.display, canon(node.func, m.aliases))
    # module/class-level sites outside any function body
    for node in ast.walk(m.tree):
        if (isinstance(node, ast.Call) and id(node) not in seen
                and canon(node.func, m.aliases) in _JIT_NAMES):
            add(node, "<module>", canon(node.func, m.aliases))
    return out


# -- R007 ---------------------------------------------------------------------

# attrs holding these are synchronization/thread-safe objects, not shared
# mutable state — touching them unlocked is the point of having them
_THREAD_SAFE_CTORS = {
    "threading.Lock", "threading.RLock", "threading.Event",
    "threading.Condition", "threading.Semaphore", "threading.BoundedSemaphore",
    "threading.Barrier", "threading.local", "queue.Queue", "queue.SimpleQueue",
    "queue.LifoQueue", "queue.PriorityQueue", "collections.deque",
}


def _r007_state(index: PackageIndex):
    """Package-level worker-thread reachability, computed once per index:
    every function passed as a ``threading.Thread(target=...)`` /
    ``Supervisor.spawn(name, run)`` target, closed over resolved calls."""
    cached = getattr(index, "_r007_state", None)
    if cached is not None:
        return cached
    from .lockorder import _LockPass

    lp = _LockPass(index)
    roots: dict[str, str] = {}  # fn qualname -> spawn-site description
    for m in index.modules:
        for fn in _iter_functions(m):
            for node in _body_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                target = how = None
                cname = canon(node.func, m.aliases)
                if cname is not None and (
                    cname == "threading.Thread" or cname.endswith(".Thread")
                ):
                    for kw in node.keywords:
                        if kw.arg == "target":
                            target, how = kw.value, "Thread target"
                elif isinstance(node.func, ast.Attribute) and node.func.attr == "spawn":
                    if len(node.args) >= 2:
                        target, how = node.args[1], "spawn target"
                    else:
                        for kw in node.keywords:
                            if kw.arg in ("run", "target"):
                                target, how = kw.value, "spawn target"
                if target is None:
                    continue
                ref = index.resolve_func_ref(m, fn, target)
                if ref is not None:
                    roots.setdefault(ref, f"{how} at {m.path}:{node.lineno}")
    thread_side = dict(roots)
    frontier = list(roots)
    while frontier:
        q = frontier.pop()
        info = index.functions.get(q)
        if info is None:
            continue
        for callee in info.calls:
            if callee not in thread_side:
                thread_side[callee] = thread_side[q]
                frontier.append(callee)
    state = (lp, thread_side)
    index._r007_state = state
    return state


def _self_accesses(lp, m: ModuleIndex, fn: FunctionInfo):
    """Yield (attr, node, kind, locked) for every ``self.X`` access in fn.
    ``locked`` is True when the access sits inside a ``with`` whose
    context binds to a known lock (the R005 lock-site index)."""
    def walk(node, locked):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            child_locked = locked
            if isinstance(child, (ast.With, ast.AsyncWith)):
                if any(lp.bind(i.context_expr, m, fn) for i in child.items):
                    child_locked = True
            if (isinstance(child, ast.Attribute)
                    and isinstance(child.value, ast.Name)
                    and child.value.id == "self"):
                kind = "write" if isinstance(child.ctx, (ast.Store, ast.Del)) else "read"
                yield child.attr, child, kind, locked
            yield from walk(child, child_locked)
    yield from walk(fn.node, False)


def _r007_safe_attrs(lp, m: ModuleIndex, cls: str) -> set:
    """Attrs of ``cls`` that are locks or thread-safe containers."""
    safe = {lid.rsplit(".", 1)[-1] for lid in lp.locks if lid.startswith(f"{cls}.")}
    for node in ast.walk(m.tree):
        if not (isinstance(node, ast.ClassDef) and node.name == cls):
            continue
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Assign) and isinstance(sub.value, ast.Call)):
                continue
            if canon(sub.value.func, m.aliases) in _THREAD_SAFE_CTORS:
                for t in sub.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        safe.add(t.attr)
    return safe


def _r007(index: PackageIndex, m: ModuleIndex) -> list[Finding]:
    lp, thread_side = _r007_state(index)
    if not thread_side:
        return []
    out: list[Finding] = []
    for cls in m.methods:
        safe = None  # computed lazily, only for classes with thread-side writes
        writes: dict[str, tuple] = {}   # attr -> (fn, node) unlocked thread-side write
        reads: dict[str, list] = {}     # attr -> [(fn, node)] unlocked foreign reads
        for fn in _iter_functions(m):
            if fn.class_name != cls:
                continue
            on_thread = fn.qualname in thread_side
            if not on_thread and fn.node.name == "__init__":
                continue  # runs before the thread exists
            for attr, node, kind, locked in _self_accesses(lp, m, fn):
                if locked:
                    continue
                if on_thread and kind == "write":
                    if safe is None:
                        safe = _r007_safe_attrs(lp, m, cls)
                    if attr not in safe:
                        writes.setdefault(attr, (fn, node))
                elif not on_thread and kind == "read":
                    reads.setdefault(attr, []).append((fn, node))
        for attr in sorted(set(writes) & set(reads)):
            wfn, wnode = writes[attr]
            rfn, rnode = min(reads[attr], key=lambda t: t[1].lineno)
            out.append(Finding(
                rule="R007", file=m.path, line=rnode.lineno,
                qualname=rfn.display, snippet=m.snippet(rnode),
                message=(
                    f"'{cls}.{attr}' is written by worker thread "
                    f"{wfn.display} (line {wnode.lineno}, "
                    f"{thread_side[wfn.qualname]}) and read here with no "
                    "lock held on either side — torn/stale reads under churn"
                ),
            ))
    return out


_RULES = {"R001": _r001, "R002": _r002, "R003": _r003, "R004": _r004,
          "R006": _r006, "R007": _r007}


def run_rules(index: PackageIndex, rules: set | None = None) -> list[Finding]:
    out: list[Finding] = []
    for m in index.modules:
        for rid, impl in _RULES.items():
            if rules is None or rid in rules:
                out.extend(impl(index, m))
    return out
