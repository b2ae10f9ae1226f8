"""The decoder cores share sub-layers through models/layers.py and never
through one another: a core can be read, changed or removed alone."""
import ast
import os

import pytest

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "models")
CORES = ("lfm2", "ouro", "deepseek_v3", "afmoe", "jamba", "mellum")


def _sibling_imports(path):
    """Modules of paddle_tpu/models that the file imports, by a relative
    name or by the package's own."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level == 1:
                mod = f"paddle_tpu.models.{mod}".rstrip(".")
            elif node.level:
                continue
            names = [mod] + [f"{mod}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            parts = name.split(".")
            if parts[:2] == ["paddle_tpu", "models"] and len(parts) > 2:
                out.add(parts[2])
    return out


@pytest.mark.parametrize("core", CORES)
def test_a_core_imports_layers_and_no_other_core(core):
    siblings = _sibling_imports(os.path.join(MODELS, f"{core}.py"))
    assert "layers" in siblings
    assert not siblings & set(CORES)
    # and the library stands under them all
    assert not _sibling_imports(os.path.join(MODELS, "layers.py"))


@pytest.mark.parametrize("core", CORES)
def test_only_the_core_that_was_taught_them_takes_the_residual_streams(core):
    """`layers.hc_*` are the library's; a core takes them by importing
    them, and today one does (models/deepseek_v3.py, `hc_mult`)."""
    with open(os.path.join(MODELS, f"{core}.py")) as f:
        takes = "hc_coefficients" in f.read()
    assert takes is (core == "deepseek_v3")
