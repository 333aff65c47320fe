"""The rule registry.  Every rule is one class with a ``code``, a
``summary`` (shown by ``--list-rules``), per-rule ``defaults`` merged
under ``podlint.toml``'s ``[rule.<CODE>]`` table, and a ``check``
yielding :class:`Finding`s.  Register with ``@register``.

The catalog is distilled from this repo's actual bug history — see
tools/podlint/README.md for the incident each rule pins.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import ClassVar, Dict, Iterator, List, Optional, Set, Tuple, Type

from .analysis import ModuleModel, dotted_name
from .crossmodule import PL007_DEFAULTS, PL008_DEFAULTS


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.code} {self.message}"


def callee_name(call: ast.Call) -> Tuple[Optional[str], Optional[str]]:
    """-> (dotted-or-approximate display name, last component).

    Unlike :func:`dotted_name`, survives subscript chains:
    ``self.buffers[pid].put`` -> ("...put", "put").
    """
    name = dotted_name(call.func)
    if name:
        return name, name.split(".")[-1]
    if isinstance(call.func, ast.Attribute):
        return f"...{call.func.attr}", call.func.attr
    return None, None


class Rule:
    code: str = ""
    summary: str = ""
    defaults: ClassVar[Dict[str, object]] = {}

    def check(self, model: ModuleModel,
              cfg: Dict[str, object]) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, model: ModuleModel, node: ast.AST,
                message: str) -> Finding:
        return Finding(model.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0) + 1, self.code, message)


REGISTRY: Dict[str, Type[Rule]] = {}


def register(cls: Type[Rule]) -> Type[Rule]:
    REGISTRY[cls.code] = cls
    return cls


# ---------------------------------------------------------------------------
# PL001 — dtype drift
# ---------------------------------------------------------------------------


@register
class DtypeDrift(Rule):
    """Array creation without an explicit dtype in a jnp-importing module.

    ``jnp.zeros(shape)`` silently means float32 — or float64 once
    somebody flips ``jax_enable_x64`` — so a carry built this way
    upcasts a bf16 pipeline the first time it meets real data (the
    PR 2 / PR 4 / PR 6 bf16-carry class).  Carries must follow
    ``f.dtype``; constants must say what they are.
    """

    code = "PL001"
    summary = "jnp.zeros/ones/full/empty without an explicit dtype"
    defaults: ClassVar[Dict[str, object]] = {
        "ops": ["zeros", "ones", "full", "empty"],
    }
    # positional arity at which dtype is present: zeros(shape, dtype),
    # full(shape, fill_value, dtype)
    _DTYPE_POS: ClassVar[Dict[str, int]] = {"zeros": 2, "ones": 2, "empty": 2, "full": 3}

    def check(self, model, cfg):
        if not model.jnp_aliases:
            return
        ops = set(cfg["ops"])
        for node in ast.walk(model.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name or "." not in name:
                continue
            head, _, op = name.rpartition(".")
            if head not in model.jnp_aliases or op not in ops:
                continue
            if any(kw.arg == "dtype" for kw in node.keywords):
                continue
            if len(node.args) >= self._DTYPE_POS.get(op, 2):
                continue
            yield self.finding(
                model, node,
                f"dtype-drift: {head}.{op}(...) without an explicit dtype "
                f"defaults to float32 (float64 under x64) — pass dtype= "
                f"(carries follow f.dtype)")


# ---------------------------------------------------------------------------
# PL002 — lock discipline
# ---------------------------------------------------------------------------


@register
class LockDiscipline(Rule):
    """Blocking calls inside ``with <lock>:`` bodies.

    PR 5's deadlock: ``PodRouter.put`` enqueued into a ``block``-policy
    buffer while holding the router lock; the thing that frees buffer
    space mid-handoff is ``migrate()`` — which needs that same lock.
    Condition ``wait``/``wait_for`` on the guarding lock is fine (it
    releases while waiting) and is not in the default blocklist.
    """

    code = "PL002"
    summary = "blocking call (put/recv/join/sleep/...) under a held lock"
    defaults: ClassVar[Dict[str, object]] = {
        "lock_glob": "*lock*",
        "blocking": ["put", "block_until_ready", "recv", "recv_into",
                     "send", "sendall", "accept", "connect", "join",
                     "sleep", "device_get"],
    }

    def check(self, model, cfg):
        blocking = set(cfg["blocking"])
        for with_node, lock_expr in model.lock_regions(cfg["lock_glob"]):
            lock_name = dotted_name(
                lock_expr.func if isinstance(lock_expr, ast.Call)
                else lock_expr) or "<lock>"
            for call in self._calls_in_region(with_node):
                name, last = callee_name(call)
                if last is None or last not in blocking:
                    continue
                # "sep".join(...) is a string op, not a thread join
                if (last == "join" and isinstance(call.func, ast.Attribute)
                        and isinstance(call.func.value, ast.Constant)):
                    continue
                yield self.finding(
                    model, call,
                    f"lock-discipline: {name}(...) may block while "
                    f"`{lock_name}` is held — a waiter that needs this "
                    f"lock to make progress deadlocks (move the call "
                    f"outside the critical section)")

    @staticmethod
    def _calls_in_region(with_node: ast.With) -> Iterator[ast.Call]:
        """Calls lexically executed under the lock: skips nested function
        bodies (closures usually run later, lock released)."""
        def walk(node: ast.AST) -> Iterator[ast.Call]:
            for child in ast.iter_child_nodes(node):
                if isinstance(child,
                              (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
                    continue
                if isinstance(child, ast.Call):
                    yield child
                yield from walk(child)

        for stmt in with_node.body:
            if isinstance(stmt, ast.Call):
                yield stmt
            yield from walk(stmt)


# ---------------------------------------------------------------------------
# PL003 — use after donate
# ---------------------------------------------------------------------------


@register
class UseAfterDonate(Rule):
    """Reading a variable after passing it through a donating jit call.

    ``jax.jit(f, donate_argnums=(0,))`` hands the argument's buffer to
    XLA; on a real accelerator the old array is dead afterwards, and a
    later read returns garbage or raises — while on CPU (tests!) it
    silently works.  The canonical repair is rebinding the name to the
    result: ``state, _ = advance(state, ...)``.
    """

    code = "PL003"
    summary = "variable read again after being donated to a jit call"
    defaults: ClassVar[Dict[str, object]] = {
        # extra callee names known to donate, "name:pos[,pos]" — for
        # donating programs built in another module/function (podlint's
        # inference is per-function)
        "donating": [],
    }

    def check(self, model, cfg):
        extra: Dict[str, Set[int]] = {}
        if model.repo is not None:
            # attributes holding a donating program, inferred repo-wide
            # (e.g. `self._advance = _advance_for(...)`)
            extra.update(model.repo.donating_attrs)
        for spec in cfg["donating"]:
            name, _, nums = str(spec).partition(":")
            extra[name] = ({int(p) for p in nums.split(",") if p.strip()}
                           or {0})
        returns = (model.repo.returns_donating
                   if model.repo is not None else {})
        for info in model.functions.values():
            yield from self._check_function(model, info.node, extra, returns)

    # -- helpers -------------------------------------------------------------
    @staticmethod
    def _donated_positions(call: ast.Call) -> Optional[Set[int]]:
        """``jax.jit(..., donate_argnums=...)`` -> the donated positions
        (None when this is not a donating-jit expression)."""
        name = dotted_name(call.func)
        if not name or name.split(".")[-1] not in ("jit", "pjit"):
            return None
        for kw in call.keywords:
            if kw.arg not in ("donate_argnums", "donate_argnames"):
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                out = {e.value for e in v.elts
                       if isinstance(e, ast.Constant)
                       and isinstance(e.value, int)}
                return out or {0}
            return {0}  # unresolvable expression: assume arg 0
        return None

    def _check_function(self, model, fn, extra, returns=None
                        ) -> Iterator[Finding]:
        donating: Dict[str, Set[int]] = dict(extra)
        returns = returns or {}
        consumed: Dict[str, Tuple[str, int]] = {}  # name -> (callee, line)

        def scan_expr(node: ast.AST) -> Iterator[Finding]:
            """Reads first (depth-first), then consumption effects."""
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue  # closures: conservative skip
                yield from scan_expr(child)
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in consumed):
                callee, line = consumed[node.id]
                yield self.finding(
                    model, node,
                    f"use-after-donate: `{node.id}` was donated to "
                    f"`{callee}` at line {line} and read again — its "
                    f"buffer belongs to XLA now (rebind the name to the "
                    f"call's result)")
            if isinstance(node, ast.Call):
                callee, last = callee_name(node)
                positions = None
                if last is not None and last in donating:
                    positions = donating[last]
                elif (isinstance(node.func, ast.Call)
                      and self._donated_positions(node.func) is not None):
                    callee = dotted_name(node.func.func) or "jit(...)"
                    positions = self._donated_positions(node.func)
                if positions:
                    for p in positions:
                        if p < len(node.args) and isinstance(
                                node.args[p], ast.Name):
                            consumed[node.args[p].id] = (
                                callee, node.lineno)

        def bind(target: ast.AST) -> None:
            for n in ast.walk(target):
                if isinstance(n, ast.Name):
                    consumed.pop(n.id, None)

        def scan_stmt(stmt: ast.stmt) -> Iterator[Finding]:
            if isinstance(stmt, ast.Assign):
                yield from scan_expr(stmt.value)
                # a donating-jit expression bound to a local name makes
                # that name a donating callee for the rest of the body
                if (isinstance(stmt.value, ast.Call)
                        and self._donated_positions(stmt.value) is not None):
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            donating[t.id] = self._donated_positions(
                                stmt.value)
                elif isinstance(stmt.value, ast.Call):
                    # `advance = self._advance_fn()` where _advance_fn
                    # is known (repo-wide) to return a donating program
                    _, last = callee_name(stmt.value)
                    pos = returns.get(last or "")
                    if pos:
                        for t in stmt.targets:
                            if isinstance(t, ast.Name):
                                donating[t.id] = pos
                for t in stmt.targets:
                    bind(t)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                if stmt.value is not None:
                    yield from scan_expr(stmt.value)
                if isinstance(stmt, ast.AugAssign):
                    yield from scan_expr(stmt.target)  # aug reads too
                bind(stmt.target)
            elif isinstance(stmt, ast.For):
                yield from scan_expr(stmt.iter)
                bind(stmt.target)
                for s in stmt.body + stmt.orelse:
                    yield from scan_stmt(s)
            elif isinstance(stmt, (ast.If, ast.While)):
                yield from scan_expr(stmt.test)
                for s in stmt.body + stmt.orelse:
                    yield from scan_stmt(s)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    yield from scan_expr(item.context_expr)
                    if item.optional_vars is not None:
                        bind(item.optional_vars)
                for s in stmt.body:
                    yield from scan_stmt(s)
            elif isinstance(stmt, ast.Try):
                for s in (stmt.body + stmt.orelse + stmt.finalbody
                          + [h for hh in stmt.handlers for h in hh.body]):
                    yield from scan_stmt(s)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                return  # nested scopes are visited as their own functions
            else:
                yield from scan_expr(stmt)

        for stmt in fn.body:
            yield from scan_stmt(stmt)


# ---------------------------------------------------------------------------
# PL004 — host sync in a hot path
# ---------------------------------------------------------------------------


@register
class HostSyncInHotPath(Rule):
    """``float()`` / ``.item()`` / ``np.asarray`` on values inside traced
    functions.

    Inside a trace these either raise (``TracerConversionError``) or —
    worse, on the op-by-op fallback paths — force a device
    round-trip per item, turning the fused pod step back into the
    per-item dispatch loop the kernels exist to avoid.
    """

    code = "PL004"
    summary = "host sync (float()/.item()/np.asarray) in traced code"
    defaults: ClassVar[Dict[str, object]] = {
        "sync_methods": ["item", "tolist"],
        "sync_builtins": ["float", "int", "bool"],
    }
    _STATIC_ATTRS: ClassVar[Set[str]] = {"shape", "ndim", "dtype", "size"}  # trace-time values

    def check(self, model, cfg):
        sync_methods = set(cfg["sync_methods"])
        sync_builtins = set(cfg["sync_builtins"])
        for info in model.traced_functions():
            static = self._static_names(info.node)
            for node in self._own_nodes(info.node):
                if not isinstance(node, ast.Call):
                    continue
                name, last = callee_name(node)
                if name is None:
                    continue
                parts = name.split(".")
                hit = None
                if last in sync_methods and len(parts) > 1:
                    hit = f".{last}()"
                elif (len(parts) == 1 and parts[0] in sync_builtins
                      and node.args
                      and not self._static_arg(node.args[0], static)):
                    hit = f"{parts[0]}()"
                elif (len(parts) == 2 and parts[0] in model.np_aliases
                      and parts[1] in ("asarray", "array")):
                    hit = f"{name}()"
                elif parts[-1] in ("device_get", "block_until_ready"):
                    hit = f"{name}()"
                if hit:
                    yield self.finding(
                        model, node,
                        f"host-sync-in-hot-path: {hit} inside traced "
                        f"function `{info.qualname}` ({info.traced_via}) "
                        f"— forces a device round-trip per call (keep "
                        f"values on device; convert outside the trace)")

    @staticmethod
    def _static_arg(arg: ast.AST, static: Set[str] = frozenset()) -> bool:
        """float(x.shape[0]) and friends are trace-time constants — as
        are names derived from them (``B, S, d = x.shape; int(B * S)``)."""
        if isinstance(arg, ast.Constant):
            return True
        return any(
            (isinstance(n, ast.Attribute)
             and n.attr in HostSyncInHotPath._STATIC_ATTRS)
            or (isinstance(n, ast.Name) and n.id in static)
            for n in ast.walk(arg))

    @staticmethod
    def _static_names(fn: ast.AST) -> Set[str]:
        """Names assigned from shape-derived (trace-time constant)
        expressions — a fixpoint mirroring PL005's taint, with the
        opposite sign."""
        static: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for node in HostSyncInHotPath._own_nodes(fn):
                if not isinstance(node, ast.Assign):
                    continue
                if not HostSyncInHotPath._static_arg(node.value, static):
                    continue
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id not in static:
                            static.add(n.id)
                            changed = True
        return static

    @staticmethod
    def _own_nodes(fn: ast.AST) -> Iterator[ast.AST]:
        """Body nodes excluding nested defs (those are traced functions
        of their own and get visited separately)."""
        def walk(node):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                yield child
                yield from walk(child)

        for stmt in fn.body:
            yield stmt
            yield from walk(stmt)


# ---------------------------------------------------------------------------
# PL005 — Python branch on a tracer
# ---------------------------------------------------------------------------


@register
class TracerBranch(Rule):
    """Python ``if``/``while`` on jnp array truthiness in traced code.

    Under a trace this raises ``TracerBoolConversionError`` at best; at
    worst (concrete sub-values) it silently bakes one branch into the
    compiled program.  Control flow on traced values belongs to
    ``jnp.where`` / ``jax.lax.cond`` / ``jax.lax.while_loop``.
    """

    code = "PL005"
    summary = "Python if/while on a traced array value"

    def check(self, model, cfg):
        if not model.jnp_aliases:
            return
        for info in model.traced_functions():
            tainted = self._tainted_names(model, info.node)
            for node in HostSyncInHotPath._own_nodes(info.node):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                why = self._array_test(model, node.test, tainted)
                if why:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield self.finding(
                        model, node,
                        f"tracer-branch: Python `{kind}` on {why} inside "
                        f"traced function `{info.qualname}` "
                        f"({info.traced_via}) — use jnp.where / "
                        f"jax.lax.cond / jax.lax.while_loop")

    def _tainted_names(self, model, fn) -> Set[str]:
        """Names assigned (anywhere in the function) from jnp.* calls or
        from expressions over already-tainted names — a cheap forward
        taint, no flow sensitivity."""
        tainted: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for node in HostSyncInHotPath._own_nodes(fn):
                if not isinstance(node, ast.Assign):
                    continue
                if not self._is_arrayish(model, node.value, tainted):
                    continue
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id not in tainted:
                            tainted.add(n.id)
                            changed = True
        return tainted

    @staticmethod
    def _walk_pruned(node: ast.AST) -> Iterator[ast.AST]:
        """ast.walk, but subtrees rooted at a trace-time-constant
        attribute (``q.shape[1] > 1``) are skipped — reading an array's
        shape/dtype is a static test even when the array is traced."""
        if (isinstance(node, ast.Attribute)
                and node.attr in HostSyncInHotPath._STATIC_ATTRS):
            return
        yield node
        for child in ast.iter_child_nodes(node):
            yield from TracerBranch._walk_pruned(child)

    def _is_arrayish(self, model, expr, tainted) -> bool:
        for n in self._walk_pruned(expr):
            if isinstance(n, ast.Call):
                name = dotted_name(n.func)
                if name and name.split(".")[0] in model.jnp_aliases:
                    return True
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
        return False

    def _array_test(self, model, test, tainted) -> Optional[str]:
        """None when the test looks static; else a description."""
        # `x is None` / isinstance() / pure-attribute tests are the
        # legitimate static-branch idioms — never flag them
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return None
        if isinstance(test, ast.Call):
            name = dotted_name(test.func)
            if name == "isinstance":
                return None
            if name and name.split(".")[0] in model.jnp_aliases:
                return f"`{ast.unparse(test)}` (a jnp array)"
        for n in self._walk_pruned(test):
            if isinstance(n, ast.Call):
                name = dotted_name(n.func)
                if name and name.split(".")[0] in model.jnp_aliases:
                    return f"`{ast.unparse(n)}` (a jnp array)"
            if isinstance(n, ast.Name) and n.id in tainted:
                return f"`{n.id}` (assigned from jnp ops)"
        return None


# ---------------------------------------------------------------------------
# PL006 — metric recording / span entry in traced code
# ---------------------------------------------------------------------------


@register
class MetricInTrace(Rule):
    """``counter.inc()`` / ``hist.observe()`` / ``obs.span(...)`` /
    ``obs.stage(...)`` inside traced functions.

    Telemetry executed under a trace is the worst kind of wrong: it does
    not crash.  The recording call runs once per *compile*, not per
    step — the counter undercounts forever, and a span's duration
    measures tracing time, then never fires again.  The rule of
    DESIGN.md §13 is "record at host-sync boundaries only": drains and
    spans belong to the host control plane (``pipeline.run``'s tail, an
    autoscaler tick, a handoff edge), never to the jitted program.
    ``obs.spans`` also no-ops under a trace at runtime; this rule is the
    static gate so the dead call never ships.

    ``set`` is deliberately NOT in ``record_methods``: flagging it would
    false-positive on every ``x.at[i].set(v)`` in traced code.  Gauge
    ``.set`` in a trace is still caught in review — it is rare; the
    at[].set idiom is everywhere.
    """

    code = "PL006"
    summary = "metric recording (.inc/.dec/.observe) or span entry in traced code"
    defaults: ClassVar[Dict[str, object]] = {
        "record_methods": ["inc", "dec", "observe"],
        "span_callables": ["span", "stage"],
    }

    def check(self, model, cfg):
        record = set(cfg["record_methods"])
        spans = set(cfg["span_callables"])
        for info in model.traced_functions():
            for node in HostSyncInHotPath._own_nodes(info.node):
                if not isinstance(node, ast.Call):
                    continue
                name, last = callee_name(node)
                if last is None:
                    continue
                if last in record and isinstance(node.func, ast.Attribute):
                    yield self.finding(
                        model, node,
                        f"metric-in-trace: {name}(...) inside traced "
                        f"function `{info.qualname}` ({info.traced_via}) "
                        f"— it records once per compile, not per step "
                        f"(keep counters as traced state and drain them "
                        f"at a host-sync boundary)")
                elif last in spans:
                    yield self.finding(
                        model, node,
                        f"metric-in-trace: span entry {name}(...) inside "
                        f"traced function `{info.qualname}` "
                        f"({info.traced_via}) — a span under a trace "
                        f"times the tracer, then never fires again "
                        f"(wrap the host call site instead)")


# ---------------------------------------------------------------------------
# PL007 — lock-order inversion (interprocedural, repo-wide)
# ---------------------------------------------------------------------------


@register
class LockOrderInversion(Rule):
    """A cycle in the static acquired-before graph.

    The graph's nodes are lock identities (``ClassName.attr``, or the
    string passed to ``concurrency.make_lock``); an edge A -> B means
    some code path acquires B while holding A — either a lexically
    nested ``with``, or a call (possibly through several modules) into
    a function that acquires B.  Two threads walking a cycle's edges in
    different orders deadlock; PR 5's put-vs-migrate hang was exactly
    the ``PodRouter._lock -> TaggedBuffer._lock`` edge meeting its
    reverse.  The full graph ships as the ``lockgraph.json`` / DOT
    artifact (``--lock-graph``, ``make analyze``); the runtime half is
    ``repro.concurrency.lockdep`` (DESIGN.md §14).
    """

    code = "PL007"
    summary = "lock-order inversion: cycle in the acquired-before graph"
    defaults: ClassVar[Dict[str, object]] = dict(PL007_DEFAULTS)

    def check(self, model, cfg):
        if model.repo is None:
            return
        for cyc in model.repo.lock_cycles():
            anchor = cyc["anchor"]
            if anchor["path"] != model.path:
                continue  # reported once, in the anchor-site's module
            order = " ; ".join(
                f'{e["src"]} -> {e["dst"]} ({e["path"]}:{e["line"]})'
                for e in cyc["edges"])
            node = ast.Module(body=[], type_ignores=[])  # line carrier
            node.lineno, node.col_offset = anchor["line"], 0
            yield self.finding(
                model, node,
                f"lock-order-inversion: the acquired-before graph has a "
                f"cycle over {{{', '.join(cyc['locks'])}}}: {order} — "
                f"two threads taking these locks in different orders "
                f"deadlock; pick one global order and restructure the "
                f"odd path out")


# ---------------------------------------------------------------------------
# PL008 — blocking call under a lock, interprocedural
# ---------------------------------------------------------------------------


@register
class BlockingReachableUnderLock(Rule):
    """Calls that *transitively* block while a lock is held.

    PL002 sees ``buffer.put(...)`` lexically inside ``with lock:`` —
    but not ``self._enqueue(sid)`` where ``_enqueue`` (possibly in
    another module) is the thing that calls ``put``.  This rule walks
    the repo call graph: a function is *blocking* if it contains a
    blocking primitive or calls a blocking function; invoking one with
    any lock held is flagged, with the full witness chain down to the
    primitive.  Raw primitives under a lexical lock stay PL002's
    finding — each defect is reported by exactly one rule.

    Closures defined under ``with lock:`` and invoked in the same
    region resolve like any other callee, which closes PL002's
    nested-def blind spot.  ``cond.wait[_for]`` on the sole held lock
    is exempt (the wait releases it); waiting while *another* lock is
    also held is flagged — that lock stays held for the wait's
    unbounded duration.
    """

    code = "PL008"
    summary = "call that transitively blocks while a lock is held"
    defaults: ClassVar[Dict[str, object]] = dict(PL008_DEFAULTS)

    def check(self, model, cfg):
        if model.repo is None:
            return
        for ev in model.repo.region_data(model)[1]:
            held = ", ".join(f"`{h}`" for h in ev.held)
            if ev.kind == "blocking":
                yield self.finding(
                    model, ev.node,
                    f"blocking-under-lock: call into `{ev.target}` may "
                    f"block ({ev.chain}) while {held} is held — a "
                    f"waiter that needs that lock to free capacity "
                    f"deadlocks (move the call outside the critical "
                    f"section)")
            elif ev.kind == "wait-extra":
                yield self.finding(
                    model, ev.node,
                    f"blocking-under-lock: waiting on condition "
                    f"`{ev.target}` releases only its own lock — "
                    f"{held} stays held for the wait's unbounded "
                    f"duration (drop the outer lock first)")
