"""Function-level facts: held-lock scopes, writes, resolved calls.

One walk per function produces everything the rules need:

- the with-stack of held locks at every point (lock identity resolved
  through the model: `self._lock` via the class MRO, `cs.out_cv` via
  the unique declaring class, `with lock:` through local aliases),
- every write to an attribute (assign / augassign / del / subscript
  store / known mutating method call) with the held stack at that
  point — the guarded-by lint's raw material,
- every call with the held stack at that point plus its resolved
  target(s) — the lock-order graph's raw material. Resolution is
  deliberately conservative: `self.m()` through the MRO, typed
  attributes through the inferred `attr_types`, module-alias calls
  through import tracking and return annotations, and a unique-name
  fallback ONLY when the name is defined exactly once in the analyzed
  set. Ambiguous names (`close`, `get`, `put`, ...) resolve to nothing
  — a missing edge is recoverable by the runtime sanitizer; a wrong
  edge would fail the build on a phantom deadlock.
"""

from __future__ import annotations

import ast
import dataclasses

from tools.analyze.model import (
    ClassInfo, Model, ModuleInfo, caller_holds, is_locked_decorated)

# deque/list/set/dict methods that mutate their receiver
MUTATORS = {
    "append", "appendleft", "extend", "extendleft", "insert", "pop",
    "popleft", "popitem", "clear", "remove", "discard", "add", "update",
    "setdefault", "sort", "reverse",
}


@dataclasses.dataclass
class Held:
    lock_id: str | None      # "Class.attr" / "module.NAME"; None=unresolved
    base: str                # unparsed base expr ("self", "cs", "o.cs", "")
    line: int


@dataclasses.dataclass
class Write:
    field: str
    base: str                # "" for module globals
    kind: str                # assign|augassign|del|store|mutcall
    line: int
    held: list[Held]
    base_cls: str | None = None  # inferred class of the base expression


@dataclasses.dataclass
class CallSite:
    targets: list[str]       # resolved function ids (possibly empty)
    attr: str                # the called name (diagnostics)
    line: int
    held: list[Held]


@dataclasses.dataclass
class FunctionFacts:
    fid: str                 # "Class.method" or "mod.py:func"
    owner: ClassInfo | None
    module: ModuleInfo
    node: ast.FunctionDef
    acquires: list[tuple[str, int]] = dataclasses.field(
        default_factory=list)                     # (lock_id, line)
    nested: list[tuple[str, str, int]] = dataclasses.field(
        default_factory=list)                     # (outer, inner, line)
    writes: list[Write] = dataclasses.field(default_factory=list)
    calls: list[CallSite] = dataclasses.field(default_factory=list)
    assumed_held: list[str] = dataclasses.field(default_factory=list)
    unresolved_with: int = 0


def owner_class_of(owner) -> ClassInfo | None:
    return owner if isinstance(owner, ClassInfo) else None


def class_mro(model: Model, cls: ClassInfo | None):
    seen, order, stack = set(), [], [cls]
    while stack:
        c = stack.pop(0)
        if c is None or c.name in seen:
            continue
        seen.add(c.name)
        order.append(c)
        stack.extend(model.classes.get(b) for b in c.bases)
    return order


def _find_method(model: Model, cls: ClassInfo | None, name: str):
    for c in class_mro(model, cls):
        if name in c.methods:
            return c, c.methods[name]
    return None


def _return_class(fn: ast.FunctionDef) -> str | None:
    from tools.analyze.model import _ann_class
    return _ann_class(fn.returns)


class _Walker:
    def __init__(self, model: Model, owner, fn: ast.FunctionDef,
                 fid: str):
        self.model = model
        self.owner = owner
        self.cls = owner_class_of(owner)
        self.module: ModuleInfo = (owner.module if self.cls is not None
                                   else owner)
        self.fn = fn
        self.facts = FunctionFacts(fid, self.cls, self.module, fn)
        self.aliases: dict[str, ast.expr] = {}   # local = self.lock_attr
        self.local_defs: dict[str, ast.FunctionDef] = {}
        self.param_types: dict[str, str | None] = {}
        self.held: list[Held] = []

    # -- lock identity --

    def resolve_lock_expr(self, ctx: ast.expr):
        """(lock_id | None, base_text) for a with-context expression."""
        if isinstance(ctx, ast.Name):
            target = self.aliases.get(ctx.id)
            if target is not None:
                return self.resolve_lock_expr(target)
            decl = self.module.locks.get(ctx.id)
            if decl is not None:
                return decl.lock_id, ""
            return None, ctx.id
        if isinstance(ctx, ast.Attribute):
            base_txt = ast.unparse(ctx.value)
            if base_txt == "self":
                decl = self.model.find_lock(self.cls, ctx.attr)
            else:
                decl = self.model.find_lock(None, ctx.attr)
                if decl is None:
                    t = self._expr_class(ctx.value)
                    if t is not None:
                        decl = self.model.find_lock(t, ctx.attr)
            return (decl.lock_id if decl else None), base_txt
        return None, ast.unparse(ctx)

    # -- type inference on expressions --

    def _expr_class(self, expr: ast.expr) -> ClassInfo | None:
        if isinstance(expr, ast.Name):
            t = self.param_types.get(expr.id)
            return self.model.classes.get(t) if t else None
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and expr.value.id == "self" \
                and self.cls is not None:
            for c in class_mro(self.model, self.cls):
                t = c.attr_types.get(expr.attr)
                if t is None:
                    continue
                return self._type_to_class(t)
        if isinstance(expr, ast.Attribute):
            # one level of attribute typing through a typed base
            # (`o.cs` where o: _StagedOp and _StagedOp.cs: _ConnState)
            base_cls = self._expr_class(expr.value)
            if base_cls is not None:
                for c in class_mro(self.model, base_cls):
                    t = c.attr_types.get(expr.attr)
                    if t is not None:
                        return self._type_to_class(t)
        if isinstance(expr, ast.Subscript):
            base = expr.value
            if isinstance(base, ast.Attribute) and \
                    isinstance(base.value, ast.Name) and \
                    base.value.id == "self" and self.cls is not None:
                for c in class_mro(self.model, self.cls):
                    t = c.attr_types.get(base.attr)
                    if isinstance(t, tuple) and t[0] == "list":
                        return self._type_to_class(t[1])
        return None

    def _type_to_class(self, t) -> ClassInfo | None:
        if isinstance(t, str):
            return self.model.classes.get(t)
        if isinstance(t, tuple) and t[0] == "factory":
            # `self.x = alias.fn(...)`: resolve fn via aliases + return
            # annotation (e.g. `tele.scope(...) -> Scope`)
            fname = t[2]
            for cand in self.model.by_name.get(fname, []):
                own, fn = cand
                if isinstance(own, ModuleInfo):
                    rc = _return_class(fn)
                    if rc and rc in self.model.classes:
                        return self.model.classes[rc]
            return None
        return None

    # -- call target resolution --

    def resolve_call(self, node: ast.Call) -> tuple[list[str], str]:
        f = node.func
        if isinstance(f, ast.Name):
            name = f.id
            if name in self.local_defs:
                return [f"{self.facts.fid}.<local>.{name}"], name
            if name in self.module.functions:
                return [f"{self.module.path}:{name}"], name
            if name in self.model.classes and (
                    name in self.module.classes
                    or name in self.module.aliases):
                ci = self.model.classes[name]
                hit = _find_method(self.model, ci, "__init__")
                if hit:
                    return [f"{hit[0].name}.__init__"], name
                return [], name
            src = self.module.aliases.get(name)
            if src and ":" in src:
                # `from M import name` — find it in the analyzed set
                modpath, fname = src.split(":", 1)
                for cand in self.model.by_name.get(fname, []):
                    own, _fn = cand
                    if isinstance(own, ModuleInfo) and \
                            _mod_matches(own, modpath):
                        return [f"{own.path}:{fname}"], name
                if fname in self.model.classes:
                    hit = _find_method(self.model,
                                       self.model.classes[fname],
                                       "__init__")
                    if hit:
                        return [f"{hit[0].name}.__init__"], name
            return [], name
        if not isinstance(f, ast.Attribute):
            return [], "<expr>"
        name = f.attr
        base = f.value
        # self.method()
        if isinstance(base, ast.Name) and base.id == "self" \
                and self.cls is not None:
            hit = _find_method(self.model, self.cls, name)
            if hit:
                return [f"{hit[0].name}.{name}"], name
            return [], name
        # module_alias.func()
        if isinstance(base, ast.Name) and base.id in self.module.aliases \
                and base.id not in self.param_types:
            modpath = self.module.aliases[base.id]
            for cand in self.model.by_name.get(name, []):
                own, _fn = cand
                if isinstance(own, ModuleInfo) and _mod_matches(own, modpath):
                    return [f"{own.path}:{name}"], name
        # typed attribute / element
        t = self._expr_class(base)
        if t is not None:
            hit = _find_method(self.model, t, name)
            if hit:
                return [f"{hit[0].name}.{name}"], name
            return [], name
        # unique-name fallback: exactly one definition in the whole set
        cands = self.model.by_name.get(name, [])
        if len(cands) == 1:
            own, _fn = cands[0]
            if isinstance(own, ClassInfo):
                return [f"{own.name}.{name}"], name
            return [f"{own.path}:{name}"], name
        return [], name

    # -- the walk --

    def run(self) -> FunctionFacts:
        fn = self.fn
        from tools.analyze.model import _ann_class
        for a in fn.args.args + fn.args.kwonlyargs:
            self.param_types[a.arg] = _ann_class(a.annotation)
        held0: list[Held] = []
        for lock_attr in caller_holds(fn, self.module.lines):
            decl = self.model.find_lock(self.cls, lock_attr)
            held0.append(Held(decl.lock_id if decl else None, "self",
                              fn.lineno))
        if is_locked_decorated(fn):
            decl = self.model.find_lock(self.cls, "_lock")
            lid = decl.lock_id if decl else None
            held0.append(Held(lid, "self", fn.lineno))
            if lid:
                self.facts.acquires.append((lid, fn.lineno))
        if fn.name.endswith("_locked") and self.cls is not None:
            for c in class_mro(self.model, self.cls):
                for attr in c.locks:
                    held0.append(Held(c.locks[attr].lock_id, "self",
                                      fn.lineno))
        self.facts.assumed_held = [h.lock_id for h in held0 if h.lock_id]
        self.held = held0
        for stmt in fn.body:
            self._visit(stmt)
        return self.facts

    def _visit(self, node: ast.AST) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.local_defs[node.name] = node
            return                       # analyzed separately, empty held
        if isinstance(node, ast.Lambda):
            return
        if isinstance(node, ast.With):
            self._visit_with(node)
            return
        if isinstance(node, ast.Assign):
            self._record_alias(node)
            for tgt in node.targets:
                self._record_write_target(tgt, "assign", node.lineno)
            self._visit_expr(node.value)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._record_write_target(node.target, "assign", node.lineno)
                self._visit_expr(node.value)
            return
        if isinstance(node, ast.AugAssign):
            self._record_write_target(node.target, "augassign", node.lineno)
            self._visit_expr(node.value)
            return
        if isinstance(node, ast.Delete):
            for tgt in node.targets:
                self._record_write_target(tgt, "del", node.lineno)
            return
        if isinstance(node, ast.Expr):
            self._visit_expr(node.value)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._visit_expr(child)
            else:
                self._visit(child)

    def _visit_with(self, node: ast.With) -> None:
        entered = 0
        for item in node.items:
            lid, base = self.resolve_lock_expr(item.context_expr)
            is_lockish = lid is not None or self._looks_lockish(
                item.context_expr)
            if lid is None and is_lockish:
                self.facts.unresolved_with += 1
            if is_lockish:
                h = Held(lid, base, node.lineno)
                if lid is not None:
                    self.facts.acquires.append((lid, node.lineno))
                    # same-lock pairs are kept: a lexical `with L: with
                    # L:` on a non-reentrant Lock is a certain deadlock
                    # (lockorder's self-edge check; RLock/Condition
                    # filtered there by kind)
                    for outer in self.held:
                        if outer.lock_id:
                            self.facts.nested.append(
                                (outer.lock_id, lid, node.lineno))
                self.held.append(h)
                entered += 1
            else:
                self._visit_expr(item.context_expr)
        for stmt in node.body:
            self._visit(stmt)
        for _ in range(entered):
            self.held.pop()

    def _looks_lockish(self, ctx: ast.expr) -> bool:
        """Is this with-context plausibly a lock? (attribute/name whose
        final component is a known lock attr somewhere, or matches the
        repo's lock naming: contains 'lock', '_l', or '_cv')."""
        name = None
        if isinstance(ctx, ast.Attribute):
            name = ctx.attr
        elif isinstance(ctx, ast.Name):
            tgt = self.aliases.get(ctx.id)
            if tgt is not None:
                return self._looks_lockish(tgt)
            name = ctx.id
        if name is None:
            return False
        if self.model.find_lock(self.cls, name) is not None:
            return True
        low = name.lower()
        return "lock" in low or low in ("_l",) or low.endswith("_cv")

    def _record_alias(self, node: ast.Assign) -> None:
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Attribute):
            self.aliases[node.targets[0].id] = node.value

    def _record_write_target(self, tgt: ast.expr, kind: str,
                             line: int) -> None:
        if isinstance(tgt, ast.Tuple):
            for elt in tgt.elts:
                self._record_write_target(elt, kind, line)
            return
        attr_node = None
        if isinstance(tgt, ast.Attribute):
            attr_node = tgt
        elif isinstance(tgt, ast.Subscript) and \
                isinstance(tgt.value, ast.Attribute):
            attr_node = tgt.value
            kind = "store"
        if attr_node is None:
            return
        bc = self._expr_class(attr_node.value)
        self.facts.writes.append(Write(
            attr_node.attr, ast.unparse(attr_node.value), kind, line,
            list(self.held), bc.name if bc is not None else None))

    def _visit_expr(self, node: ast.expr) -> None:
        # manual traversal, NOT ast.walk: walk yields a pruned node's
        # children anyway, so `continue` alone would still attribute
        # calls inside a merely-CONSTRUCTED lambda to the current held
        # set — a deferred body that never runs under these locks would
        # fabricate lock-order edges (phantom deadlocks)
        stack = [node]
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.Lambda, ast.FunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(sub))
            if isinstance(sub, ast.Call):
                targets, name = self.resolve_call(sub)
                self.facts.calls.append(
                    CallSite(targets, name, sub.lineno, list(self.held)))
                # mutating method call on an attribute
                f = sub.func
                if isinstance(f, ast.Attribute) and f.attr in MUTATORS \
                        and isinstance(f.value, ast.Attribute):
                    bc = self._expr_class(f.value.value)
                    self.facts.writes.append(Write(
                        f.value.attr, ast.unparse(f.value.value),
                        "mutcall", sub.lineno, list(self.held),
                        bc.name if bc is not None else None))


def _mod_matches(mi: ModuleInfo, dotted: str) -> bool:
    """Does module info `mi` correspond to dotted path `pkg.mod` (or the
    `pkg.mod:name` form's module part)?"""
    dotted = dotted.split(":", 1)[0]
    tail = dotted.split(".")[-1]
    base = mi.path.rsplit("/", 1)[-1]
    return base == f"{tail}.py" or base == tail


def analyze_functions(model: Model) -> dict[str, FunctionFacts]:
    """FunctionFacts for every function/method (plus locals) in the set."""
    out: dict[str, FunctionFacts] = {}

    def _run(owner, fn: ast.FunctionDef, fid: str):
        w = _Walker(model, owner, fn, fid)
        facts = w.run()
        out[fid] = facts
        for name, sub in w.local_defs.items():
            _run(owner, sub, f"{fid}.<local>.{name}")

    for mi in model.modules.values():
        for fname, fn in mi.functions.items():
            _run(mi, fn, f"{mi.path}:{fname}")
        for ci in mi.classes.values():
            for mname, fn in ci.methods.items():
                _run(ci, fn, f"{ci.name}.{mname}")
    return out
