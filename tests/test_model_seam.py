"""The seam between ``paddle_tpu/serving/`` and ``paddle_tpu/models/``: a
config names its own description (``cfg.serving_description()``), the
serving package imports no model family, and no served family imports
another (what they share lives in ``models/blocks.py``).
"""
import ast
import os
import subprocess
import sys

import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.serving.model import describe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "paddle_tpu")

# module under paddle_tpu/models -> (its tiny preset, its description)
FAMILIES = {
    "gpt": ("GPT_TINY", "gpt_serving", "GPTServing"),
    "jamba": ("JAMBA_TINY", "jamba", "JambaServing"),
    "kimi_k2": ("KIMI_K2_TINY", "kimi_k2", "KimiK2Serving"),
    "olmo_hybrid": ("OLMO_HYBRID_TINY", "olmo_hybrid", "OlmoHybridServing"),
    "cohere2_moe": ("COHERE2_MOE_TINY", "cohere2_moe", "Cohere2MoeServing"),
    "solar_open2": ("SOLAR_OPEN2_TINY", "solar_open2", "SolarOpen2Serving"),
}
DESCRIPTION_MODULES = sorted({home for _, home, _ in FAMILIES.values()})

REQUIRED = ("cfg", "vocab_size", "max_positions", "cache_pools", "recurrent",
            "state_geometry", "paged_kernel", "hold", "embed",
            "prefill_layers", "decode_layers", "logits", "forward")


def _module(name):
    import importlib

    return importlib.import_module(f"paddle_tpu.models.{name}")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_config_names_its_description(family):
    preset, home, cls = FAMILIES[family]
    cfg = getattr(_module(family), preset)
    model = describe(cfg)
    assert type(model) is getattr(_module(home), cls)
    assert model.cfg is cfg
    missing = [a for a in REQUIRED if not hasattr(model, a)]
    assert not missing, missing
    if model.paged_kernel:
        assert callable(model.kernel_takes_pages)
    # a description is handed through as it is
    assert describe(model) is model
    # the verify window's layers are the GPT block's alone
    assert hasattr(model, "verify_layers") == (family == "gpt")


def test_a_seventh_family_edits_nothing_under_serving():
    """A config class nobody under ``serving/`` has heard of: ``describe``
    takes its word, and an engine is built from it."""
    from paddle_tpu.models import gpt as G
    from paddle_tpu.models.gpt_serving import GPTServing

    class Stub:
        prefill_layers = decode_layers = None

    stub = Stub()

    class SeventhConfig:
        def serving_description(self):
            return stub

    assert describe(SeventhConfig()) is stub

    class SeventhServing(GPTServing):
        pass

    class ServedSeventhConfig:
        def serving_description(self):
            return SeventhServing(G.GPT_TINY)

    eng = serving.DecodeEngine(
        G.init_params(jax.random.PRNGKey(0), G.GPT_TINY),
        ServedSeventhConfig(),
        serving.EngineConfig(max_batch=2, max_seq=16, prefill_buckets=(8,),
                             page_size=8))
    assert type(eng.model) is SeventhServing and eng.cfg is G.GPT_TINY


def test_what_names_no_description_is_a_type_error():
    with pytest.raises(TypeError, match="no model description for dict; "
                       "pass an object with the surface serving/model.py "
                       "lists"):
        describe({})


def test_importing_serving_loads_no_description():
    code = ("import sys, paddle_tpu.serving; print(sorted("
            "m for m in sys.modules if m.startswith('paddle_tpu.models.')))")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, check=True,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}).stdout
    loaded = ast.literal_eval(out.strip().splitlines()[-1])
    assert not {f"paddle_tpu.models.{m}" for m in DESCRIPTION_MODULES} & set(
        loaded), loaded


def _imports(path):
    """(node, is it at module level) of every import of a file."""
    tree = ast.parse(open(path).read())
    inside = {id(n) for scope in ast.walk(tree)
              if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.ClassDef))
              for n in ast.walk(scope)}
    return [(n, id(n) not in inside) for n in ast.walk(tree)
            if isinstance(n, (ast.Import, ast.ImportFrom))]


def _names_models(node):
    """Whether an import inside ``paddle_tpu/serving/`` reaches
    ``paddle_tpu.models``."""
    if isinstance(node, ast.Import):
        return any(a.name.startswith("paddle_tpu.models") for a in node.names)
    module = node.module or ""
    if node.level == 0:
        return module.startswith("paddle_tpu.models") or (
            module == "paddle_tpu"
            and any(a.name == "models" for a in node.names))
    return node.level == 2 and (module.split(".")[0] == "models" or (
        not module and any(a.name == "models" for a in node.names)))


def test_serving_imports_no_model_when_it_is_loaded():
    found = []
    folder = os.path.join(PKG, "serving")
    for name in sorted(os.listdir(folder)):
        if name.endswith(".py"):
            found += [(name, top) for node, top in _imports(
                os.path.join(folder, name)) if _names_models(node)]
    # what is left: the replica process's entry point builds the GPT model
    # it serves, inside the function
    assert found == [("replica.py", False)], found


def _sibling_modules(node):
    """Modules of ``paddle_tpu/models`` that an import inside it names."""
    if isinstance(node, ast.Import):
        return {a.name.split(".")[2] for a in node.names
                if a.name.startswith("paddle_tpu.models.")}
    module = node.module or ""
    if node.level == 0 and module.startswith("paddle_tpu.models"):
        module = module[len("paddle_tpu.models"):].lstrip(".")
    elif node.level != 1:
        return set()
    return {module.split(".")[0]} if module else {a.name for a in node.names}


def test_no_served_family_imports_another():
    served = sorted(set(FAMILIES) - {"gpt"} | {"gpt_serving"})
    for name in served:
        reached = set().union(*(_sibling_modules(node) for node, _top in
                                _imports(os.path.join(PKG, "models",
                                                      name + ".py"))))
        allowed = {"blocks"} | ({"gpt"} if name == "gpt_serving" else set())
        assert reached <= allowed, (name, reached - allowed)
        # and each does share: the five import what is common from blocks
        assert name == "gpt_serving" or "blocks" in reached, name


def test_a_description_without_verify_layers_is_refused_the_window():
    from paddle_tpu.models import gpt as G
    from paddle_tpu.models.gpt_serving import GPTServing

    class NoWindow:
        """The GPT block's description with its verify layers taken out."""

        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            if name == "verify_layers":
                raise AttributeError(name)
            return getattr(self._inner, name)

    params = G.init_params(jax.random.PRNGKey(0), G.GPT_TINY)
    geometry = dict(max_batch=2, max_seq=16, prefill_buckets=(8,),
                    page_size=8)
    with pytest.raises(ValueError, match="NoWindow has no verify_layers: "
                       "the verify window"):
        serving.DecodeEngine(params, NoWindow(GPTServing(G.GPT_TINY)),
                             serving.EngineConfig(verify_window=2,
                                                  **geometry))
    # without the window it is served, and the GPT block's own has one
    serving.DecodeEngine(params, NoWindow(GPTServing(G.GPT_TINY)),
                         serving.EngineConfig(**geometry))
    serving.DecodeEngine(params, G.GPT_TINY,
                         serving.EngineConfig(verify_window=2, **geometry))
