"""Dead-module report: which ``repro.*`` modules are unreachable from the
public surface and the test suite.

A stdlib-``ast`` import-graph walk (no imports are executed): roots are
``repro/__init__.py``, every ``tests/test_*.py``, ``examples/``, and
the ``repro.launch`` CLIs (each is an entry point via
``python -m``).  Edges are ``import x`` / ``from x import y`` statements,
including relative imports and the lazy ``_LAZY``-table indirection used
by ``repro.analysis`` (string module paths in the module body are picked
up conservatively).  Modules never reached are reported.

``--check`` makes the report BLOCKING: any dead module not named in the
explicit :data:`ALLOWED_DEAD` allowlist fails the run (the CI
``dead-modules`` gate).  Allowlisting is a reviewed code change — add
the module name with a justification comment, not a wildcard.

Usage::

    python -m repro.launch.dead_modules --out DEAD_modules.json
    python -m repro.launch.dead_modules --check   # CI gate
"""
from __future__ import annotations

import argparse
import ast
import json
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set


# Modules allowed to be unreachable under --check.  Every entry needs a
# justification comment; an empty tuple means the whole tree must stay
# reachable from the public surface, the tests, or a CLI entry point.
ALLOWED_DEAD: tuple = ()


def _module_name(path: Path, src_root: Path) -> str:
    rel = path.relative_to(src_root).with_suffix("")
    parts = list(rel.parts)
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _iter_string_modules(tree: ast.AST, known: Set[str]) -> Iterable[str]:
    """String literals that name known modules (lazy-import tables)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in known:
                yield node.value


def _edges_of(path: Path, mod: str, known: Set[str]) -> Set[str]:
    try:
        tree = ast.parse(path.read_text())
    except SyntaxError:
        return set()
    out: Set[str] = set()

    def note(name: Optional[str]) -> None:
        if not name:
            return
        # register the module itself and every package prefix (importing
        # repro.analysis.verify also executes repro and repro.analysis)
        parts = name.split(".")
        for k in range(1, len(parts) + 1):
            cand = ".".join(parts[:k])
            if cand in known:
                out.add(cand)

    pkg_parts = mod.split(".") if mod else []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                note(alias.name)
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative import
                base = pkg_parts[: len(pkg_parts) - node.level + 1]
                stem = ".".join(base + ([node.module] if node.module else []))
            else:
                stem = node.module or ""
            note(stem)
            for alias in node.names:
                note(f"{stem}.{alias.name}" if stem else alias.name)
    for name in _iter_string_modules(tree, known):
        out.add(name)
    return out


def build_report(repo_root: Path) -> Dict[str, object]:
    src_root = repo_root / "src"
    files = {p for p in (src_root / "repro").rglob("*.py")}
    mods: Dict[str, Path] = {_module_name(p, src_root): p for p in files}
    known = set(mods)

    graph: Dict[str, Set[str]] = {m: _edges_of(p, m, known) for m, p in mods.items()}

    roots: Set[str] = {"repro"}
    # every launch CLI is a python -m entry point
    roots |= {m for m in known if m.startswith("repro.launch.")}
    external_roots: List[Path] = []
    for pattern in ("tests/*.py", "examples/*.py"):
        external_roots.extend(repo_root.glob(pattern))
    external_edges: Set[str] = set()
    for p in external_roots:
        external_edges |= _edges_of(p, "", known)
    roots |= external_edges

    seen: Set[str] = set()
    stack = [r for r in roots if r in known]
    while stack:
        m = stack.pop()
        if m in seen:
            continue
        seen.add(m)
        # reaching a package reaches its __init__ edges; reaching any
        # module reaches its package __init__ too
        parent = m.rsplit(".", 1)[0] if "." in m else None
        if parent in known and parent not in seen:
            stack.append(parent)
        stack.extend(graph.get(m, ()) - seen)

    dead = sorted(known - seen)
    return {
        "roots": sorted(r for r in roots if r in known),
        "module_count": len(known),
        "reachable_count": len(seen),
        "dead_modules": dead,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.dead_modules")
    ap.add_argument("--out", default=None, help="write the JSON report here")
    ap.add_argument(
        "--root", default=None, help="repo root (default: auto from this file)"
    )
    ap.add_argument(
        "--check", action="store_true",
        help="exit non-zero on dead modules outside the ALLOWED_DEAD "
             "allowlist (the CI dead-modules gate)",
    )
    args = ap.parse_args(argv)
    repo_root = Path(args.root) if args.root else Path(__file__).resolve().parents[3]
    report = build_report(repo_root)
    print(
        f"[dead-modules] {report['reachable_count']}/{report['module_count']} "
        f"modules reachable; {len(report['dead_modules'])} dead"
    )
    for m in report["dead_modules"]:
        flag = " (allowlisted)" if m in ALLOWED_DEAD else ""
        print(f"    {m}{flag}")
    if args.out:
        Path(args.out).write_text(json.dumps(report, indent=1))
        print(f"[dead-modules] report -> {args.out}")
    if args.check:
        unexpected = [m for m in report["dead_modules"] if m not in ALLOWED_DEAD]
        stale = [m for m in ALLOWED_DEAD if m not in report["dead_modules"]]
        for m in unexpected:
            print(
                f"[dead-modules] FAIL: {m} is unreachable and not "
                f"allowlisted — wire it in or add it to ALLOWED_DEAD "
                f"with a justification",
                file=sys.stderr,
            )
        for m in stale:
            print(
                f"[dead-modules] FAIL: allowlist entry {m} is reachable "
                f"(or gone) — remove the stale entry",
                file=sys.stderr,
            )
        return 1 if (unexpected or stale) else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
