"""The kernel path imports downwards only: `ops/`, `gluon/` and `parallel/`
are what every cell's program is traced from, and nothing there may reach up
into the tooling that inspects programs (`analysis/`, `tools/`) or into a
tile cache (`tune/`, deleted in PR 30: a kernel's tile is a constant beside
the kernel)."""
import ast
import glob
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "mxnet_tpu")
BARRED = {"tune", "analysis", "tools"}


def _imported(path):
    """Absolute dotted names of everything the module at `path` imports,
    at any depth (function-level imports included)."""
    pkg = os.path.relpath(os.path.dirname(path), ROOT).split(os.sep)
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            base = base + (node.module.split(".") if node.module else [])
            for a in node.names:
                yield ".".join(base + [a.name]), node.lineno


def test_kernel_path_imports_no_tooling():
    found = []
    for sub in ("ops", "gluon", "parallel"):
        for path in glob.glob(os.path.join(PACKAGE, sub, "**", "*.py"),
                              recursive=True):
            for dotted, line in _imported(path):
                parts = dotted.split(".")
                if parts[0] == "tools" or (
                        parts[0] == "mxnet_tpu" and parts[1:2] and
                        parts[1] in BARRED):
                    found.append(f"{os.path.relpath(path, ROOT)}:{line} "
                                 f"imports {dotted}")
    assert not found, "\n".join(found)
