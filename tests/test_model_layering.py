"""The model layer's imports point one way: `trunk`, `experts` and `mla`
(what more than one trunk shares) <- the trunks <- what wraps them (`minilm`,
`cross_encoder`, `ops/knn.py`).  The imports are read from the sources
with `ast`, every one a module makes anywhere in it, and those cases
import no jax; the packed programs' names are read from their lowered
text."""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "pathway_tpu", "models")

TRUNKS = ("transformer", "moe_mla", "eva", "moe_hybrid", "zaya", "decoder", "longcat")
# the modules of pathway_tpu.models below every trunk
SHARED = ("trunk", "experts", "mla", "tokenizer")

# configuration class -> the name the device trace knows its packed program by
PACKED = {
    "moe_mla": ("MoeMlaConfig", "_fwd_packed_moe_mla"),
    "eva": ("EvaConfig", "_fwd_packed_eva"),
    "moe_hybrid": ("MoeHybridConfig", "_fwd_packed_moe_hybrid"),
    "zaya": ("ZayaConfig", "_fwd_packed_zaya"),
    "longcat": ("LongcatConfig", "_fwd_packed_longcat"),
}


def models_imported(module: str) -> set:
    """The modules of pathway_tpu.models that `module` imports, at its top
    or inside a function."""
    tree = ast.parse(open(os.path.join(MODELS, module + ".py")).read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "pathway_tpu.models":
                found.update(a.name for a in node.names)
            elif node.module.startswith("pathway_tpu.models."):
                found.add(node.module.split(".")[2])
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("pathway_tpu.models."):
                    found.add(a.name.split(".")[2])
    return found - {module}


@pytest.mark.parametrize("module", TRUNKS)
def test_a_trunk_imports_only_the_shared_layer(module):
    assert models_imported(module) <= set(SHARED), models_imported(module)


@pytest.mark.parametrize("module", ("trunk", "experts", "mla"))
def test_the_shared_layer_imports_no_trunk(module):
    allowed = {"trunk": {"tokenizer"}, "experts": {"trunk"}, "mla": {"trunk"}}[module]
    assert models_imported(module) <= allowed, models_imported(module)


@pytest.mark.parametrize("module", sorted(PACKED))
def test_every_packed_decoder_trunk_is_the_one_lm_class(module):
    """`model_module(config).LM` is `trunk.PackedTrunkLM` for each packed
    decoder trunk, and no module defines an LM class of its own."""
    import importlib

    from pathway_tpu.models import trunk

    config = getattr(importlib.import_module(f"pathway_tpu.models.{module}"), PACKED[module][0])()
    assert trunk.model_module(config).LM is trunk.PackedTrunkLM
    tree = ast.parse(open(os.path.join(MODELS, module + ".py")).read())
    assert not [n.name for n in tree.body if isinstance(n, ast.ClassDef) and n.name.endswith("LM")]


@pytest.mark.parametrize("module", sorted(PACKED))
def test_the_packed_program_keeps_its_name(module):
    """The name chipbench's `programs` lists read from the device trace:
    the jitted program's, as `PackedTrunkLM` makes it from `PACKED`."""
    import importlib

    import jax
    import jax.numpy as jnp

    model = importlib.import_module(f"pathway_tpu.models.{module}")
    assert model.PACKED.program == PACKED[module][1]
    # lowered, not run: the parameters are their shapes
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), model.TINY))
    lm = model.LM(model.TINY, params=params)
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int16)
    text = lm._packed_jit.lower(params, ids, ids, 2).as_text()
    assert text.startswith(f"module @jit_{PACKED[module][1]} ")
