"""Self-contained lint gate (no external linters in the image).

Checks, in the spirit of the reference's clang-format CI gate
(.github/workflows/clang-format.yml): every file must parse, imports must be
used, no tabs / trailing whitespace / overlong lines.

Run: ``python ci/lint.py`` (exit 1 on findings); ``--json`` emits the same
machine-readable report shape as ``ci/analyze.py --json``.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import List

from analyze import Finding, emit_github, emit_json

MAX_LINE = 100
ROOTS = ["spark_rapids_jni_tpu", "tests", "__graft_entry__.py",
         "chip_smoke.py", "ci", "tools"]

_URL_RE = re.compile(r"https?://\S+")


def _overlong_without_urls(line: str) -> bool:
    """True if the line is overlong even with its URLs removed: only an
    actual URL earns the long-line exemption, not any line that happens
    to mention http."""
    return len(_URL_RE.sub("", line)) > MAX_LINE


def iter_py_files(repo_root: str):
    for root in ROOTS:
        path = os.path.join(repo_root, root)
        if os.path.isfile(path):
            yield path
        else:
            for dirpath, dirnames, filenames in os.walk(path):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for f in filenames:
                    if f.endswith(".py"):
                        yield os.path.join(dirpath, f)


class _ImportChecker(ast.NodeVisitor):
    """Unused-import detection: imported names never referenced.

    Names listed in ``__all__`` string literals count as used (re-exports).
    """

    def __init__(self):
        self.imported = {}  # name -> lineno
        self.used = set()

    def _collect_strings(self, node):
        """Names from any expression built of list/tuple literals and +."""
        if isinstance(node, (ast.List, ast.Tuple)):
            for elt in node.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    self.used.add(elt.value)
        elif isinstance(node, ast.BinOp):
            self._collect_strings(node.left)
            self._collect_strings(node.right)

    def visit_Assign(self, node):
        if any(isinstance(t, ast.Name) and t.id == "__all__"
               for t in node.targets):
            self._collect_strings(node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node):  # __all__ += [...]
        if isinstance(node.target, ast.Name) and node.target.id == "__all__":
            self._collect_strings(node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):  # __all__: list = [...]
        if (isinstance(node.target, ast.Name)
                and node.target.id == "__all__" and node.value is not None):
            self._collect_strings(node.value)
        self.generic_visit(node)

    def visit_Import(self, node):
        for a in node.names:
            name = (a.asname or a.name).split(".")[0]
            self.imported[name] = node.lineno

    def visit_ImportFrom(self, node):
        if node.module == "__future__":
            return
        for a in node.names:
            if a.name == "*":
                continue
            self.imported[a.asname or a.name] = node.lineno

    def visit_Name(self, node):
        self.used.add(node.id)

    def visit_Attribute(self, node):
        self.generic_visit(node)


def check_file(path: str, repo_root: str) -> List[Finding]:
    rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
    findings: List[Finding] = []
    with open(path, "rb") as f:
        raw = f.read()
    try:
        text = raw.decode("utf-8")
    except UnicodeDecodeError as e:
        return [Finding("encoding", rel, 1,
                        f"not valid UTF-8 at byte {e.start}")]
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        return [Finding("syntax-error", rel, e.lineno or 1,
                        f"syntax error: {e.msg}")]

    for i, line in enumerate(text.splitlines(), 1):
        if "noqa" in line:
            continue
        if "\t" in line:
            findings.append(Finding("tab", rel, i, "tab character"))
        if line != line.rstrip():
            findings.append(Finding("trailing-whitespace", rel, i,
                                    "trailing whitespace"))
        if len(line) > MAX_LINE and _overlong_without_urls(line):
            findings.append(Finding("long-line", rel, i,
                                    f"line too long ({len(line)})"))

    chk = _ImportChecker()
    chk.visit(tree)
    # __init__.py re-exports are used by importers, not the module itself
    if not path.endswith("__init__.py"):
        for name, lineno in chk.imported.items():
            if name not in chk.used:
                findings.append(Finding("unused-import", rel, lineno,
                                        f"unused import {name!r}"))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--format", choices=("text", "json", "github"),
                    default=None,
                    help="report format (--json is shorthand for json)")
    args = ap.parse_args(argv)
    fmt = args.format or ("json" if args.as_json else "text")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings: List[Finding] = []
    n = 0
    for path in iter_py_files(repo_root):
        n += 1
        findings.extend(check_file(path, repo_root))
    if fmt == "json":
        emit_json(findings, tool="lint", files=n)
    elif fmt == "github":
        emit_github(findings, tool="lint")
    else:
        for f in findings:
            print(f"{f.path}:{f.line}: {f.message}")
        print(f"lint: {n} files, {len(findings)} findings")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
