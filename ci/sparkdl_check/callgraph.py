"""Whole-program call graph with per-function effect summaries.

PR 6's strongest rules were file-local: ``lock-blocking`` followed one
level of *same-file* call depth, and ``host-sync``/``recompile-hazard``
could not see a device sync or an unfingerprinted engine wrap hidden one
import away.  This module gives every rule the same whole-program view:
one :class:`CallGraph` per run (built lazily from the already-parsed
:class:`~ci.sparkdl_check.core.FileContext` set — no file is re-read or
re-parsed) resolving

- **module-level functions** — bare calls, ``mod.f()`` through
  ``import``/``import … as`` aliases, and ``from mod import f``
  (absolute and relative) chains;
- **class methods** — ``self.m()`` within a class, ``ClassName.m()``,
  and ``obj.m()`` where ``obj`` was assigned ``ClassName(...)`` (module
  scope, function locals, or ``self._attr = ClassName(...)``);
- **nested functions** — own nodes (their bodies run when *called*, not
  where defined), reachable from the enclosing scope by bare name.

Each node carries local **effect summaries**, the facts interprocedural
rules query transitively:

=============  ==========================================================
effect         meaning
=============  ==========================================================
blocks         the body can block indefinitely / for seconds: untimed
               ``Queue.get/put`` / ``future.result()`` / ``.join()`` /
               ``Event.wait()``, ``time.sleep``, ``subprocess.run``-family
               (``Condition.wait`` is sanctioned — it *releases* the lock)
host_sync      forces a device→host sync: ``jax.device_get`` /
               ``jax.block_until_ready`` / ``x.block_until_ready()``
compiles       resolves an engine program (``<engine>.program(...)`` may
               AOT-compile for seconds)
wraps_anon     wraps an engine program with no ``fingerprint=`` at all —
               every call of this function mints a fresh ``anon:<n>``
               compile-cache key
acquires       lock ids acquired via ``with`` in the body
=============  ==========================================================

Resolution is *sound-for-linting*, not complete: an edge we cannot
resolve (higher-order callbacks, inheritance across files, getattr) is
simply absent — rules miss it rather than guessing.  Traversal is
cycle-tolerant (visited set) and bounded (:data:`MAX_DEPTH` hops), and
:meth:`CallGraph.transitive_effect` returns the full call chain so a
finding can print *why* the flagged call is dangerous.
"""

from __future__ import annotations

import ast
from collections import deque
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Set, Tuple

from ci.sparkdl_check.astutil import dotted_name, keyword, is_engine_receiver, target_name

#: how many call hops an effect may travel before we stop looking; deep
#: enough for serving → engine → executor, shallow enough to stay fast
MAX_DEPTH = 4

_LOCK_CTORS = {"Lock", "RLock"}
_SEMAPHORE_CTORS = {"Semaphore", "BoundedSemaphore"}
_SUBPROCESS_BLOCKING = {"run", "call", "check_call", "check_output"}


# ---------------------------------------------------------------------------
# per-file lock / queue / event / condition inventory (shared with the
# lock-discipline and exception-safety rules)
# ---------------------------------------------------------------------------

class FileLockState:
    """Lock-ish objects of one file, keyed by the spelling used at the
    assignment site within a class (or module) scope."""

    def __init__(self, relpath: str):
        self.relpath = relpath
        # (class_qualname, spelling) -> lock id
        self.locks: Dict[Tuple[str, str], str] = {}
        # spellings of Condition objects (their .wait releases the lock)
        self.conditions: Set[Tuple[str, str]] = set()
        self.events: Set[Tuple[str, str]] = set()
        self.queues: Set[Tuple[str, str]] = set()
        self.semaphores: Set[Tuple[str, str]] = set()
        self.time_aliases: Set[str] = set()
        self.sleep_aliases: Set[str] = set()

    def lock_id(self, scopes: Sequence[str], spelling: str) -> Optional[str]:
        """Resolve a with-statement expression to a lock id, innermost
        class scope outward, then module scope."""
        for scope in reversed(scopes):
            hit = self.locks.get((scope, spelling))
            if hit:
                return hit
        return self.locks.get(("<module>", spelling))

    def _in_scopes(self, table, scopes: Sequence[str], spelling: str) -> bool:
        return any((s, spelling) in table for s in reversed(scopes)) or (
            ("<module>", spelling) in table
        )

    def is_condition(self, scopes, spelling):
        return self._in_scopes(self.conditions, scopes, spelling)

    def is_event(self, scopes, spelling):
        return self._in_scopes(self.events, scopes, spelling)

    def is_queue(self, scopes, spelling):
        return self._in_scopes(self.queues, scopes, spelling)

    def is_semaphore(self, scopes, spelling):
        return self._in_scopes(self.semaphores, scopes, spelling)

    def is_lock_like(self, scopes, spelling):
        """Anything with acquire()/release() pairing semantics."""
        return (
            self.lock_id(scopes, spelling) is not None
            or self.is_condition(scopes, spelling)
            or self.is_semaphore(scopes, spelling)
        )


def _ctor_name(value: ast.AST) -> Optional[str]:
    """'Lock' for threading.Lock()/Lock(), 'Queue' for queue.Queue()…"""
    if not isinstance(value, ast.Call):
        return None
    name = dotted_name(value.func)
    if name is None:
        return None
    return name.split(".")[-1]


def collect_lock_state(tree: ast.Module, relpath: str) -> FileLockState:
    """The file's lock state, collected once a parsed tree: the call graph
    and three rules ask for it, and none of them changes it."""
    memo = tree.__dict__.setdefault("_lock_states", {})
    if relpath not in memo:
        memo[relpath] = _collect_lock_state(tree, relpath)
    return memo[relpath]


def _collect_lock_state(tree: ast.Module, relpath: str) -> FileLockState:
    state = FileLockState(relpath)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "time":
                    state.time_aliases.add(a.asname or "time")
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name == "sleep":
                    state.sleep_aliases.add(a.asname or "sleep")

    def visit(node: ast.AST, class_stack: List[str]):
        scope = class_stack[-1] if class_stack else "<module>"
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else (
                [node.target] if node.target is not None else []
            )
            value = node.value
            ctor = _ctor_name(value) if value is not None else None
            for tgt in targets:
                spelling = target_name(tgt)
                if spelling is None or ctor is None:
                    continue
                key = (scope, spelling)
                if ctor in _LOCK_CTORS:
                    state.locks[key] = f"{relpath}:{scope}:{spelling}"
                elif ctor == "Condition":
                    state.conditions.add(key)
                    # Condition(self._lock) guards the underlying lock;
                    # a bare Condition() owns a fresh one
                    under = None
                    if value.args:
                        under_spelling = dotted_name(value.args[0])
                        if under_spelling is not None:
                            under = state.locks.get((scope, under_spelling))
                    state.locks[key] = (
                        under or f"{relpath}:{scope}:{spelling}"
                    )
                elif ctor in _SEMAPHORE_CTORS:
                    state.semaphores.add(key)
                elif ctor == "Event":
                    state.events.add(key)
                elif ctor in {"Queue", "SimpleQueue", "LifoQueue",
                              "PriorityQueue"}:
                    state.queues.add(key)
        new_stack = class_stack
        if isinstance(node, ast.ClassDef):
            new_stack = class_stack + [node.name]
        for child in ast.iter_child_nodes(node):
            visit(child, new_stack)

    visit(tree, [])
    return state


def blocking_reason(call: ast.Call, state: FileLockState,
                    scopes: Sequence[str]) -> Optional[str]:
    """Why ``call`` can block indefinitely (or for seconds), or None.
    ``Condition.wait`` is sanctioned — it releases the lock while
    waiting; timed variants of everything are sanctioned too."""
    fn = call.func
    name = dotted_name(fn)
    # time.sleep (with import aliasing)
    if isinstance(fn, ast.Attribute) and fn.attr == "sleep":
        if isinstance(fn.value, ast.Name) and fn.value.id in state.time_aliases:
            return "time.sleep"
    if isinstance(fn, ast.Name) and fn.id in state.sleep_aliases:
        return "time.sleep"
    if name in ("jax.device_get", "jax.block_until_ready"):
        return f"{name.split('.')[-1]} (device sync)"
    if name is not None and name.startswith("subprocess."):
        if name.split(".")[-1] in _SUBPROCESS_BLOCKING:
            return name
    if not isinstance(fn, ast.Attribute):
        return None
    recv_spelling = dotted_name(fn.value)
    attr = fn.attr
    if attr == "block_until_ready" and not call.args:
        return ".block_until_ready() (device sync)"
    if attr == "result" and not call.args and keyword(call, "timeout") is None:
        return "future.result() with no timeout"
    if attr == "join" and not call.args and keyword(call, "timeout") is None:
        return ".join() with no timeout"
    if attr == "wait" and not call.args and keyword(call, "timeout") is None:
        if recv_spelling is not None:
            # Condition.wait RELEASES the lock while waiting — sanctioned
            if state.is_condition(scopes, recv_spelling):
                return None
            if state.is_event(scopes, recv_spelling):
                return "Event.wait() with no timeout"
        return None
    if attr in ("get", "put") and recv_spelling is not None:
        if state.is_queue(scopes, recv_spelling):
            block_kw = keyword(call, "block")
            nonblocking = (
                isinstance(block_kw, ast.Constant) and block_kw.value is False
            )
            if keyword(call, "timeout") is None and not nonblocking:
                return f"Queue.{attr} without a timeout"
    return None


# ---------------------------------------------------------------------------
# the graph
# ---------------------------------------------------------------------------

class FunctionInfo:
    """One function/method node: identity, local effects, resolved
    callees."""

    __slots__ = ("qname", "relpath", "name", "display", "node",
                 "calls", "effects", "acquires")

    def __init__(self, qname: str, relpath: str, name: str, display: str,
                 node: ast.AST):
        self.qname = qname
        self.relpath = relpath
        self.name = name          # bare name
        self.display = display    # e.g. "ProgramCache.program"
        self.node = node
        #: resolved call sites: (lineno, callee qname)
        self.calls: List[Tuple[int, str]] = []
        #: effect kind -> human reason ("subprocess.run", "device_get …")
        self.effects: Dict[str, str] = {}
        #: lock ids acquired via ``with`` inside this body
        self.acquires: Set[str] = set()


class _FileSummary:
    """Intermediate per-file facts the resolver needs."""

    def __init__(self, relpath: str):
        self.relpath = relpath
        # import alias -> dotted module ("np" -> "numpy")
        self.imports: Dict[str, str] = {}
        # from-imported name -> (dotted module, original name)
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        # function qname -> FunctionInfo (includes methods, nested defs)
        self.functions: Dict[str, FunctionInfo] = {}
        # class name -> {method bare name -> qname}
        self.classes: Dict[str, Dict[str, str]] = {}
        # instance spelling -> class dotted name ("self._cache" -> "ProgramCache")
        self.instances: Dict[str, str] = {}
        self.lock_state: Optional[FileLockState] = None
        self.module_names: Set[str] = set()


def _module_names_for(relpath: str) -> Set[str]:
    """Dotted module names a package-relative path answers to.  Scanned
    files live under the ``sparkdl_tpu`` package in the real repo, but
    fixture trees import through the same dotted paths — register both
    the rooted and the bare spelling."""
    parts = relpath[:-3].split("/") if relpath.endswith(".py") else relpath.split("/")
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    if not parts:
        return set()
    bare = ".".join(parts)
    return {bare, f"sparkdl_tpu.{bare}"}


class CallGraph:
    """The whole-program view.  Build once per run from the parsed
    files; query via :meth:`callee_of` / :meth:`transitive_effect`."""

    def __init__(self, files: Dict[str, "object"]):
        # files: relpath -> FileContext (duck-typed: .tree, .relpath)
        self.functions: Dict[str, FunctionInfo] = {}
        self._summaries: Dict[str, _FileSummary] = {}
        # dotted module name -> relpath
        self._modules: Dict[str, str] = {}
        # (relpath, id(call node)) -> callee qname
        self._callsites: Dict[Tuple[str, int], str] = {}
        # file -> set of files it calls into (file-level projection)
        self._file_edges: Dict[str, Set[str]] = {}
        self._file_closure_memo: Dict[str, FrozenSet[str]] = {}

        for relpath, ctx in files.items():
            summary = self._collect_file(relpath, ctx.tree)
            self._summaries[relpath] = summary
            for m in summary.module_names:
                self._modules[m] = relpath
        for relpath, ctx in files.items():
            self._resolve_file(self._summaries[relpath])

    # -- construction --------------------------------------------------
    def _collect_file(self, relpath: str, tree: ast.Module) -> _FileSummary:
        s = _FileSummary(relpath)
        s.module_names = _module_names_for(relpath)
        s.lock_state = collect_lock_state(tree, relpath)

        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    s.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
                    if a.asname:
                        s.imports[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom):
                module = node.module or ""
                if node.level:
                    # relative import: resolve against this file's package
                    pkg = relpath.rsplit("/", 1)[0] if "/" in relpath else ""
                    parts = pkg.split("/") if pkg else []
                    up = node.level - 1
                    if relpath.endswith("__init__.py"):
                        up -= 1
                    if up > 0:
                        parts = parts[:-up] if up <= len(parts) else []
                    base = ".".join(parts)
                    module = f"{base}.{module}".strip(".") if module else base
                for a in node.names:
                    if a.name == "*":
                        continue
                    s.from_imports[a.asname or a.name] = (module, a.name)

        def walk(node, qual: List[str], class_stack: List[str]):
            if isinstance(node, ast.ClassDef):
                s.classes.setdefault(node.name, {})
                for child in ast.iter_child_nodes(node):
                    walk(child, qual + [node.name], class_stack + [node.name])
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                display = ".".join(qual + [node.name]) or node.name
                qname = f"{relpath}::{display}"
                info = FunctionInfo(qname, relpath, node.name, display, node)
                s.functions[qname] = info
                self.functions[qname] = info
                if class_stack:
                    s.classes.setdefault(class_stack[-1], {})[node.name] = qname
                for child in ast.iter_child_nodes(node):
                    walk(child, qual + [node.name], class_stack)
                return
            # instance tracking: x = ClassName(...) / self._a = ClassName(...)
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                ctor = dotted_name(node.value.func)
                if ctor is not None:
                    for tgt in node.targets:
                        spelling = target_name(tgt)
                        if spelling is not None:
                            s.instances.setdefault(spelling, ctor)
            for child in ast.iter_child_nodes(node):
                walk(child, qual, class_stack)

        walk(tree, [], [])
        return s

    def _class_method(self, summary: _FileSummary, cls_name: str,
                      method: str) -> Optional[str]:
        """``cls_name`` may be local or imported; return the method's
        qname when the class is in a scanned file."""
        local = summary.classes.get(cls_name)
        if local is not None:
            return local.get(method)
        imported = summary.from_imports.get(cls_name)
        if imported is not None:
            module, orig = imported
            target = self._modules.get(module)
            if target is not None:
                other = self._summaries[target]
                methods = other.classes.get(orig)
                if methods is not None:
                    return methods.get(method)
        return None

    def _module_function(self, module: str, name: str) -> Optional[str]:
        relpath = self._modules.get(module)
        if relpath is None:
            return None
        target = self._summaries[relpath]
        qname = f"{relpath}::{name}"
        if qname in target.functions:
            return qname
        # re-export: from x import f inside the target module
        reexport = target.from_imports.get(name)
        if reexport is not None and reexport[0] != module:
            return self._module_function(reexport[0], reexport[1])
        return None

    def _resolve_call(self, summary: _FileSummary, call: ast.Call,
                      class_stack: List[str],
                      enclosing: List[str]) -> Optional[str]:
        spelled = dotted_name(call.func)
        if spelled is None:
            return None
        parts = spelled.split(".")
        relpath = summary.relpath
        if len(parts) == 1:
            name = parts[0]
            # nested def of an enclosing function, innermost first
            for depth in range(len(enclosing), 0, -1):
                qname = f"{relpath}::{'.'.join(enclosing[:depth] + [name])}"
                if qname in summary.functions:
                    return qname
            # method of the enclosing class called bare? no — skip
            qname = f"{relpath}::{name}"
            if qname in summary.functions:
                return qname
            imported = summary.from_imports.get(name)
            if imported is not None:
                return self._module_function(imported[0], imported[1])
            return None
        head, rest = parts[0], parts[1:]
        if head == "self" and class_stack:
            if len(rest) == 1:
                # self.m() — method of the innermost class
                for cls in reversed(class_stack):
                    hit = summary.classes.get(cls, {}).get(rest[0])
                    if hit is not None:
                        return hit
                return None
            # self._attr.m(): instance attribute of a known class
            owner = ".".join(["self"] + rest[:-1])
            cls_name = summary.instances.get(owner)
            if cls_name is not None:
                return self._class_method(
                    summary, cls_name.split(".")[-1], rest[-1]
                )
            return None
        # ClassName.m(...)
        if len(rest) == 1 and (head in summary.classes
                               or head in summary.from_imports):
            hit = self._class_method(summary, head, rest[0])
            if hit is not None:
                return hit
        # obj.m() where obj is a known instance spelling
        owner = ".".join(parts[:-1])
        cls_name = summary.instances.get(owner)
        if cls_name is not None:
            hit = self._class_method(
                summary, cls_name.split(".")[-1], parts[-1]
            )
            if hit is not None:
                return hit
        # mod.f() / pkg.mod.f() through import aliases
        if head in summary.imports:
            module = summary.imports[head]
            # try longest module match first: a.b.c -> module a.b, func c
            for split in range(len(parts) - 1, 0, -1):
                dotted_mod = ".".join([module] + parts[1:split])
                hit = self._module_function(dotted_mod, parts[split])
                if hit is not None:
                    return hit
        # from pkg import mod; mod.f()
        if head in summary.from_imports and len(rest) == 1:
            module, orig = summary.from_imports[head]
            return self._module_function(f"{module}.{orig}", rest[0])
        return None

    def _resolve_file(self, summary: _FileSummary) -> None:
        state = summary.lock_state

        for info in summary.functions.values():
            enclosing = info.display.split(".")[:-1]
            # class scope chain for lock-state lookups
            class_stack = [
                p for p in enclosing if p in summary.classes
            ]
            func_chain = [
                p for p in info.display.split(".")
                if p not in summary.classes
            ]

            def visit(node, held_class_stack):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)) and node is not info.node:
                    return  # nested bodies belong to their own nodes
                if isinstance(node, ast.ClassDef):
                    held_class_stack = held_class_stack + [node.name]
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        spelling = dotted_name(item.context_expr)
                        if spelling is not None:
                            lock = state.lock_id(held_class_stack, spelling)
                            if lock is not None:
                                info.acquires.add(lock)
                if isinstance(node, ast.Call):
                    reason = blocking_reason(node, state, held_class_stack)
                    if reason is not None:
                        info.effects.setdefault("blocks", reason)
                    sync = _host_sync_reason(node)
                    if sync is not None:
                        info.effects.setdefault("host_sync", sync)
                    if is_engine_receiver(node.func, attrs=("program",)):
                        info.effects.setdefault(
                            "compiles", "engine program resolution"
                        )
                    anon = _anon_wrap_reason(node, info)
                    if anon is not None:
                        info.effects.setdefault("wraps_anon", anon)
                    callee = self._resolve_call(
                        summary, node, held_class_stack, func_chain[:-1]
                    )
                    if callee is not None and callee != info.qname:
                        info.calls.append((node.lineno, callee))
                        self._callsites[
                            (summary.relpath, id(node))
                        ] = callee
                        if self.functions[callee].relpath != info.relpath:
                            self._file_edges.setdefault(
                                info.relpath, set()
                            ).add(self.functions[callee].relpath)
                for child in ast.iter_child_nodes(node):
                    visit(child, held_class_stack)

            for child in ast.iter_child_nodes(info.node):
                visit(child, class_stack)

    # -- queries -------------------------------------------------------
    def callee_of(self, relpath: str, call: ast.Call) -> Optional[str]:
        """The resolved callee qname of a Call node from the SAME parsed
        tree the graph was built from (node identity keyed)."""
        return self._callsites.get((relpath, id(call)))

    def info(self, qname: str) -> Optional[FunctionInfo]:
        return self.functions.get(qname)

    def transitive_effect(
        self,
        qname: str,
        kind: str,
        max_depth: int = MAX_DEPTH,
        stop_relpaths: Iterable[str] = (),
    ) -> Optional[Tuple[List[FunctionInfo], str]]:
        """Shortest call chain from ``qname`` to a function whose LOCAL
        effects include ``kind``; cycle-tolerant, bounded to
        ``max_depth`` hops.  ``stop_relpaths`` prunes sanctioned files
        (e.g. the dispatch-window synchronizer) from the search.
        Returns ``(chain, reason)`` where ``chain[0]`` is ``qname``'s
        node and ``chain[-1]`` is where the effect lives, or None."""
        start = self.functions.get(qname)
        if start is None:
            return None
        stop = set(stop_relpaths)
        if start.relpath in stop:
            return None
        seen = {qname}
        queue = deque([(start, [start])])
        while queue:
            node, chain = queue.popleft()
            reason = node.effects.get(kind)
            if reason is not None:
                return chain, reason
            if len(chain) > max_depth:
                continue
            for _, callee in node.calls:
                if callee in seen:
                    continue
                seen.add(callee)
                nxt = self.functions.get(callee)
                if nxt is None or nxt.relpath in stop:
                    continue
                queue.append((nxt, chain + [nxt]))
        return None

    def format_chain(self, chain: Sequence[FunctionInfo],
                     from_relpath: Optional[str] = None) -> str:
        """``a() → b() [serving/cache.py] → c() [engine/core.py]`` —
        the file tag appears whenever the hop crosses a file (including
        the first hop, when ``from_relpath`` names the calling file)."""
        parts = []
        prev_relpath = from_relpath or (chain[0].relpath if chain else None)
        for info in chain:
            tag = (
                f" [{info.relpath}]" if info.relpath != prev_relpath else ""
            )
            parts.append(f"{info.display}(){tag}")
            prev_relpath = info.relpath
        return " → ".join(parts)

    # -- file-level projections (incremental cache + --changed-only) ---
    def file_forward_closure(self, relpath: str) -> FrozenSet[str]:
        """Every file reachable from ``relpath`` through resolved calls
        (excluding itself) — the dependency set whose content hashes key
        this file's cached interprocedural findings."""
        memo = self._file_closure_memo.get(relpath)
        if memo is not None:
            return memo
        seen: Set[str] = set()
        stack = [relpath]
        while stack:
            cur = stack.pop()
            for nxt in self._file_edges.get(cur, ()):
                if nxt not in seen and nxt != relpath:
                    seen.add(nxt)
                    stack.append(nxt)
        out = frozenset(seen)
        self._file_closure_memo[relpath] = out
        return out

    def reverse_file_dependents(
        self, relpaths: Iterable[str]
    ) -> Set[str]:
        """Files whose findings could change when ``relpaths`` change:
        every file with a call path INTO any of them (transitively)."""
        targets = set(relpaths)
        reverse: Dict[str, Set[str]] = {}
        for src, dsts in self._file_edges.items():
            for dst in dsts:
                reverse.setdefault(dst, set()).add(src)
        out: Set[str] = set()
        stack = list(targets)
        while stack:
            cur = stack.pop()
            for dep in reverse.get(cur, ()):
                if dep not in out and dep not in targets:
                    out.add(dep)
                    stack.append(dep)
        return out

    def stats(self) -> Dict[str, int]:
        return {
            "functions": len(self.functions),
            "edges": sum(len(f.calls) for f in self.functions.values()),
            "cross_file_edges": sum(
                len(v) for v in self._file_edges.values()
            ),
        }


def _host_sync_reason(call: ast.Call) -> Optional[str]:
    spelled = dotted_name(call.func)
    if spelled in ("jax.device_get", "jax.block_until_ready"):
        return spelled
    if (isinstance(call.func, ast.Attribute)
            and call.func.attr == "block_until_ready" and not call.args):
        return ".block_until_ready()"
    return None


def _anon_wrap_reason(call: ast.Call, info: FunctionInfo) -> Optional[str]:
    """An engine wrap inside this function with no ``fingerprint=`` at
    all mints a fresh anon cache key per call OF THIS FUNCTION.  Lambda
    and local-def wraps are excluded — the file-local recompile-hazard
    rule already flags those at the wrap site itself."""
    if not is_engine_receiver(call.func):
        return None
    if keyword(call, "fingerprint") is not None or not call.args:
        return None
    fn_arg = call.args[0]
    if isinstance(fn_arg, ast.Lambda):
        return None
    if isinstance(fn_arg, ast.Name):
        for sub in ast.walk(info.node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
                    sub is not info.node and sub.name == fn_arg.id):
                return None  # local-closure wrap: flagged at the site
    return "engine wrap without fingerprint="
