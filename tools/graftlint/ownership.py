"""own family: thread-ownership of server state (static half).

The declarations live with the runtime (`deneva_tpu/runtime/
ownercheck.py` — pure data, stdlib-only) so the linter and the
``owner_check=true`` runtime asserts can never drift apart.

Rules
-----
own-cross-thread-write  a function reachable from a worker entry point
                        (wire worker / retire worker) writes a
                        ServerNode attribute owned by a different role.
                        The host path's bit-identity contract is that
                        the bodies a worker may run are PURE; all state
                        mutation stays at the dispatch thread's loop
                        positions.
own-undeclared-attr     a ServerNode attribute is assigned somewhere but
                        missing from the OWNER map — the declarations
                        file must stay exhaustive or the checker (and
                        the runtime guard) silently lose coverage.
"""

from __future__ import annotations

import ast

from tools.graftlint.core import Finding, Tree, walk_funcs

SERVER_MODULE = "deneva_tpu/runtime/server.py"
SERVER_CLASS = "ServerNode"


def _load_decls():
    from deneva_tpu.runtime import ownercheck as oc
    return oc.OWNER, oc.WORKER_ENTRY, oc.MUTATORS, oc.SHARED


def _self_attr_of(node: ast.AST) -> str | None:
    """`self.X...` -> "X" (the attribute directly on self), else None."""
    chain = []
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute):
            chain.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name) and node.id == "self" and chain:
        return chain[-1]
    return None


def _class_functions(mod, class_name: str) -> dict[str, list[ast.AST]]:
    """All function defs lexically inside a class (methods AND functions
    nested in methods), by name."""
    out: dict[str, list[ast.AST]] = {}
    for fn, cls in walk_funcs(mod.tree):
        if cls == class_name:
            out.setdefault(fn.name, []).append(fn)
    return out


def _writes_of(fn: ast.AST, mutators) -> list[tuple[str, int, str]]:
    """(attr, line, how) for every write to self.<attr> in a function —
    v2: enumerated over the shared CFG core's reachable blocks, so
    writes in dead code (after a return/raise) no longer count."""
    from tools.graftlint.cfg import cfg_of, reachable_nodes
    writes: list[tuple[str, int, str]] = []
    for _stmt, node in reachable_nodes(cfg_of(fn)):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                    else [t]
                for e in elts:
                    a = _self_attr_of(e)
                    if a is not None:
                        writes.append((a, node.lineno, "assignment"))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in mutators:
            a = _self_attr_of(node.func.value)
            if a is not None:
                writes.append((a, node.lineno,
                               f".{node.func.attr}() call"))
        elif isinstance(node, (ast.Delete,)):
            for t in node.targets:
                a = _self_attr_of(t)
                if a is not None:
                    writes.append((a, node.lineno, "del"))
    return writes


def _reachable_in_class(funcs: dict[str, list[ast.AST]],
                        entry_names) -> list[ast.AST]:
    """BFS from the entry functions through `self.m(...)` calls (and
    bare-name calls to class-nested functions)."""
    seen: set[int] = set()
    order: list[ast.AST] = []
    work: list[ast.AST] = []
    for name in entry_names:
        work.extend(funcs.get(name, ()))
    while work:
        fn = work.pop()
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        order.append(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = None
            if isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "self":
                name = node.func.attr
            elif isinstance(node.func, ast.Name):
                name = node.func.id
            if name:
                work.extend(f for f in funcs.get(name, ())
                            if id(f) not in seen)
    return order


def check(tree: Tree, rel: str = SERVER_MODULE,
          class_name: str = SERVER_CLASS, owners=None, entries=None,
          mutators=None, shared=None) -> list[Finding]:
    mod = tree.module(rel)
    if mod is None:
        return []                    # fixture tree without the runtime
    if None in (owners, entries, mutators, shared):
        defaults = _load_decls()
        owners, entries, mutators, shared = (
            v if v is not None else d
            for v, d in zip((owners, entries, mutators, shared), defaults))
    findings: list[Finding] = []
    funcs = _class_functions(mod, class_name)

    # declarations must stay exhaustive
    seen_attrs: dict[str, int] = {}
    for fns in funcs.values():
        for fn in fns:
            for attr, line, how in _writes_of(fn, mutators):
                if how == "assignment" or attr in owners:
                    seen_attrs.setdefault(attr, line)
    for attr, line in sorted(seen_attrs.items()):
        if attr not in owners:
            findings.append(Finding(
                "own-undeclared-attr", rel, line,
                f"{class_name}.{attr} is assigned but missing from the "
                f"OWNER map (runtime/ownercheck.py) — declare its owning "
                f"thread role"))

    # worker call graphs must not write non-owned state
    for role, entry_names in entries.items():
        for fn in _reachable_in_class(funcs, entry_names):
            for attr, line, how in _writes_of(fn, mutators):
                owner = owners.get(attr)
                if owner in (role, shared, None):
                    continue
                findings.append(Finding(
                    "own-cross-thread-write", rel, line,
                    f"`{fn.name}` runs on the {role} worker but writes "
                    f"{class_name}.{attr} ({how}), owned by {owner} — "
                    f"staged worker code must stay pure; move the "
                    f"mutation to the dispatch loop position"))
    return findings
