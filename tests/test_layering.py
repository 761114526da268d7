"""The packages below the serving stack import only downward: the model
files, the kernels, the sharding helpers and the trainer know nothing of
`ray_tpu.llm` or `ray_tpu.serve` (proxy -> handle -> replica -> OpenAIServer
-> LLMServer -> LLMEngine -> model -> ops). Inside `ray_tpu/models/` a
family's file imports `layers.py`, `initializers.py` and no other family's
file, and those two import no family: a change to one family's file moves no
other family's programs. Read from the AST, so an import inside a function
counts like one at the top of a file."""

import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ABOVE = ("ray_tpu.llm", "ray_tpu.serve")


def _imports(path, repo=REPO):
    """(line, absolute module name) of every import in the file; `from
    package import name` gives `package.name` too, since `name` may be a
    module."""
    rel = os.path.relpath(path, repo)[:-len(".py")].split(os.sep)
    package = rel[:-1]  # also right for __init__.py: its package is its dir
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - (node.level - 1)]
                base = ".".join(up + ([base] if base else []))
            yield node.lineno, base
            for alias in node.names:
                yield node.lineno, f"{base}.{alias.name}"


def _above(module):
    return any(module == a or module.startswith(a + ".") for a in ABOVE)


@pytest.mark.parametrize("package", ["ops", "parallel", "models", "train"])
def test_package_imports_nothing_above_it(package):
    root = os.path.join(REPO, "ray_tpu", package)
    files = [os.path.join(d, f) for d, _, fs in os.walk(root)
             for f in fs if f.endswith(".py")]
    assert files, root
    found = sorted({f"{os.path.relpath(p, REPO)}:{line}"
                    for p in files for line, module in _imports(p)
                    if _above(module)})
    assert not found, f"imports of {ABOVE}: {found}"


def _family_modules():
    """{module name: file} of the model families the serving path builds."""
    from ray_tpu.models import FAMILIES

    names = {attr.partition(":")[0] for fam in FAMILIES.values()
             for attr in (fam.config, fam.model)}
    return {name: os.path.join(REPO, *name.split(".")) + ".py"
            for name in names}


def _family_imports(path, families, repo=REPO):
    """Lines of `path` that import a module of `families` other than the
    file's own."""
    own = os.path.relpath(path, repo)[:-len(".py")].replace(os.sep, ".")
    return sorted({line for line, module in _imports(path, repo)
                   for name in families if name != own
                   and (module == name or module.startswith(name + "."))})


def test_no_family_imports_another_and_the_shared_modules_import_none():
    families = _family_modules()
    assert len(families) == 9 and all(map(os.path.exists, families.values()))
    shared = [os.path.join(REPO, "ray_tpu", "models", f)
              for f in ("layers.py", "initializers.py")]
    found = {os.path.relpath(path, REPO): lines
             for path in list(families.values()) + shared
             if (lines := _family_imports(path, families))}
    assert not found, f"imports of a model family's file: {found}"


def test_the_family_walk_sees_a_neighbour_however_it_is_imported(tmp_path):
    pkg = tmp_path / "ray_tpu" / "models"
    pkg.mkdir(parents=True)
    families = ["ray_tpu.models.jamba", "ray_tpu.models.granite_hybrid"]
    src = pkg / "granite_hybrid.py"
    src.write_text("from ray_tpu.models.jamba import _conv_init\n"
                   "from ray_tpu.models import layers, jamba\n"
                   "from . import granite_hybrid\n"
                   "def f():\n    from .jamba import JambaModel\n"
                   "    import ray_tpu.models.jamba_extras\n")
    assert _family_imports(str(src), families, str(tmp_path)) == [1, 2, 5]
    shared = pkg / "layers.py"
    shared.write_text("from ray_tpu.models.initializers import kernel_init\n"
                      "from ray_tpu.models.granite_hybrid import SparseMoe\n")
    assert _family_imports(str(shared), families, str(tmp_path)) == [2]


def test_the_walk_sees_function_level_and_relative_imports(tmp_path):
    pkg = tmp_path / "ray_tpu" / "models"
    pkg.mkdir(parents=True)
    src = pkg / "m.py"
    src.write_text("def f():\n    from ray_tpu.llm._internal import paged\n"
                   "    from .. import serve\n    from ..llm import x\n"
                   "from ray_tpu import llm\n")
    hits = {line for line, m in _imports(str(src), str(tmp_path))
            if _above(m)}
    assert sorted(hits) == [2, 3, 4, 5]
