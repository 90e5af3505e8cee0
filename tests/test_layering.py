"""The library stands alone: nothing under ``src/repro`` imports a directory
or script at the root of the checkout (``bench``, ``examples``,
``chip_smoke`` and the like).  Those sit outside ``src`` and resolve only
when the working directory is the root, so such an import points the
library's dependency arrow up into the script layer."""

import ast
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
SRC = ROOT / "src" / "repro"


def _root_modules() -> set:
    names = {p.name for p in ROOT.iterdir() if p.is_dir()}
    names |= {p.stem for p in ROOT.glob("*.py")}
    return {n for n in names if n.isidentifier()} | {"bench", "examples"}


def _imported_roots(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            yield node.lineno, node.module.split(".")[0]


def test_library_imports_no_script_directory():
    forbidden = _root_modules()
    assert {"bench", "tests"} <= forbidden, forbidden
    files = sorted(SRC.rglob("*.py"))
    assert files, SRC
    bad = [f"{p.relative_to(SRC.parent)}:{line} imports {root}"
           for p in files
           for line, root in sorted(_imported_roots(ast.parse(p.read_text(), str(p))))
           if root in forbidden]
    assert not bad, "\n".join(bad)
