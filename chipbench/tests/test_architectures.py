"""The seam between the harness and a model: every configuration names an
architecture that has its three files with the functions the harness, the
comparison and the readers call; the yardstick imports nothing of the
program; a name that is not there is an error that says which are."""

import ast
import os

import pytest

from chipbench import spec

FUNCTIONS = {
    "program": {"embedder", "release"},
    "reference": {"Encoder"},
    "costs": {"flops", "weight_bytes", "activation_bytes", "resident_param_bytes",
              "embed_dim", "dry_cut"},
}
ENCODER_METHODS = {"__init__", "embed", "free"}


def _tree(path: str) -> ast.Module:
    with open(path) as f:
        return ast.parse(f.read(), path)


def _imports(tree: ast.Module) -> set:
    """Top-level packages a file imports, anywhere in it."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            found.add(node.module.split(".")[0])
    return found


def _arch_file(name: str, part: str) -> str:
    return os.path.join(spec.HERE, "architectures", name, part + ".py")


def test_every_configuration_names_an_architecture_that_has_its_files():
    bench = spec.benchmark()
    for entry in bench["configs"]:
        config = spec._load(os.path.join(spec.ROOT, entry["file"]))
        arch = spec.architecture(config)
        assert arch.name == config["architecture"] and arch.name in spec.architectures()
        for part, wanted in FUNCTIONS.items():
            tree = _tree(_arch_file(arch.name, part))
            defined = {
                n.name for n in tree.body
                if isinstance(n, (ast.FunctionDef, ast.ClassDef))
            }
            assert wanted <= defined, (arch.name, part, wanted - defined)
        encoder = next(
            n for n in _tree(_arch_file(arch.name, "reference")).body
            if isinstance(n, ast.ClassDef) and n.name == "Encoder"
        )
        methods = {n.name for n in encoder.body if isinstance(n, ast.FunctionDef)}
        assert ENCODER_METHODS <= methods
    for w in bench["workloads"]:
        assert spec.cell(w["name"]).arch.name in spec.architectures()


def test_the_yardstick_imports_nothing_of_the_program():
    shared = [os.path.join(spec.HERE, name + ".py")
              for name in ("reference", "costs", "compare", "traffic", "trace")]
    own = [_arch_file(a, part) for a in spec.architectures() for part in ("reference", "costs")]
    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cls_encoder")
    own += [os.path.join(fixture, part + ".py") for part in ("reference", "costs")]
    for path in shared + own:
        assert "pathway_tpu" not in _imports(_tree(path)), path
    for a in spec.architectures():  # and the program's side is where it is taken from
        assert "pathway_tpu" in _imports(_tree(_arch_file(a, "program")))


@pytest.mark.parametrize("config", [
    {"name": "c", "architecture": "no_such_architecture"},
    {"name": "c"},  # a missing key is no default either
    {"name": "c", "architecture": "__pycache__"},
])
def test_an_unknown_architecture_is_an_error_that_lists_the_known(config):
    with pytest.raises(spec.UnknownArchitecture) as err:
        spec.architecture(config)
    assert "bert_encoder" in str(err.value) and repr(config.get("architecture")) in str(err.value)
