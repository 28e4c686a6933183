"""The contract between the engine and a model class (``models/base.py``),
and the table that finds a class (``models/registry.py::MODEL_TYPES``):
every ``model_type`` reads into its class's config and back to its class,
the weights a seed makes are the ones every cell and reference was built
from, and the engine asks a config for nothing the base does not define.
No engine is built here.
"""

import ast
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from production_stack_tpu.models import registry
from production_stack_tpu.models.base import Model, ModelConfig
from production_stack_tpu.models.registry import MODEL_TYPES, PRESETS

REPO = pathlib.Path(__file__).resolve().parents[1]
ENGINE = REPO / "production_stack_tpu" / "engine"
P = jax.sharding.PartitionSpec

# A config.json of each model_type: the published key names alone for the
# Llama family, the benchmark's configuration (its own keys ride along
# unread) for a class that has one.
LLAMA_KEYS = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 48,
              "num_hidden_layers": 2, "num_attention_heads": 4}
CONFIG_JSON = {
    **{mt: {**LLAMA_KEYS, "model_type": mt} for mt in (
        "llama", "mistral", "qwen2", "qwen3", "mixtral", "gemma", "gemma2")},
    "nemotron_h": "perf/configs/nemotron-3-super-ep4-cut.json",
    "glm4_moe_lite": "perf/configs/glm-4.7-flash-pp6-cut.json",
    "phi4flash": "perf/configs/phi-4-mini-flash.json",
    "qwen3_next": "perf/configs/qwen3-next-ep8-cut.json",
    "mellum": "perf/configs/mellum2-ep4-cut.json",
    "ouro": "perf/configs/ouro-2.6b.json",
    "exaone_moe": "perf/configs/k-exaone-ep8-cut.json",
}
CONFIG_CLASSES = sorted({row[1] for row in MODEL_TYPES.values()},
                        key=lambda c: c.__name__)


def test_the_test_knows_every_row_of_the_table():
    assert set(CONFIG_JSON) == set(MODEL_TYPES)


@pytest.mark.parametrize("model_type", sorted(MODEL_TYPES))
def test_a_model_type_reads_into_its_config_and_finds_its_class(
        model_type, tmp_path):
    _, config_cls, model_cls = MODEL_TYPES[model_type]
    hf = CONFIG_JSON[model_type]
    if isinstance(hf, str):
        hf = json.loads((REPO / hf).read_text())
    assert hf["model_type"] == model_type
    path = tmp_path / "config.json"
    path.write_text(json.dumps(hf))
    cfg = registry.config_from_hf_json(str(path), name="x")
    assert type(cfg) is config_cls and cfg.name == "x"
    assert isinstance(cfg, ModelConfig)
    model = registry.model_for(cfg)
    assert type(model) is model_cls and model.cfg is cfg
    assert isinstance(model, Model)
    # the name the benchmark imports reads every type through the table
    from production_stack_tpu.models.llama import config_from_hf_json
    assert config_from_hf_json(str(path), name="x") == cfg


def test_an_unknown_model_type_is_refused_naming_every_row(tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps({**LLAMA_KEYS, "model_type": "gpt5"}))
    with pytest.raises(ValueError) as e:
        registry.config_from_hf_json(str(path))
    assert "unsupported model_type 'gpt5'" in str(e.value)
    for model_type in MODEL_TYPES:
        assert model_type in str(e.value)


# ``init_params(PRNGKey(0))`` of each debug preset, digested over every
# leaf's path, dtype, shape and bytes: recorded from the tree before the
# classes took ``models/base.py`` (commit 5c1941f). The benchmark's weights
# are made the same way from its seed, so a bit that moves here moves which
# experts a step touches there.
WEIGHT_DIGESTS = {
    "tiny-llama-debug":
        "3809d9a38bea864d63e310548c2add49b4920a76d5f86fb93e117c30d82feafe",
    "tiny-nemotron-h-debug":
        "b100c04d934fbdbc23a93827f4c6756d5c6c9f0441ae8c04fa5632f2044a9ec3",
    "tiny-glm4-moe-lite-debug":
        "598dbd70fe8a44bbe686b32adcf1154f27a6f46b8f302fd47da48cce5e646a58",
    "tiny-phi4flash-debug":
        "44b3f7877a6155745ab2b15a093137796f2b54e4e138b54d76735616f617f98f",
    "tiny-qwen3-next-debug":
        "1d19d138c633bf0c869c15fd9abfb82de7c020203b9f7cbc895eb3caf78d2bf4",
    # recorded when the class was added (PR 46)
    "tiny-mellum-debug":
        "68272915cccf68df88fd38bc5ccce1e14c732244c56bf70144f54fd7e610492b",
    # the dense class under the loop, the exit gate's two leaves among them:
    # recorded when the model type was added (PR 49)
    "tiny-ouro-debug":
        "2f5101ef1166a85ab4cf09f9451578f0a6ccfeb337080be3e4c59f775c87edc1",
    # every layer leaves of its own, the draft module's among them: recorded
    # when the class was added (PR 53)
    "tiny-exaone-moe-debug":
        "8a230879dcae8e48e33b78adaa43205729e076c146d13ce454cc692c700af176",
}


@pytest.mark.parametrize("preset", sorted(WEIGHT_DIGESTS))
def test_a_seed_makes_the_weights_it_always_made(preset):
    params = registry.model_for(PRESETS[preset]).init_params(
        jax.random.PRNGKey(0))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), a.dtype, a.shape):
            h.update(str(part).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[preset]


# ----------------------------------------------------------------------------
# What the engine reads, read off its source
# ----------------------------------------------------------------------------


def _is(node, *names):
    """``model_cfg`` / ``self.model_cfg`` / ``runner.model_cfg`` and so on."""
    return (isinstance(node, ast.Name) and node.id in names) or (
        isinstance(node, ast.Attribute) and node.attr in names)


def _engine_trees():
    return [(p, ast.parse(p.read_text())) for p in sorted(ENGINE.glob("*.py"))]


def engine_reads(*names):
    """Attributes the engine's source reads off an object of these names."""
    return sorted({
        node.attr for _, tree in _engine_trees() for node in ast.walk(tree)
        if isinstance(node, ast.Attribute) and _is(node.value, *names)})


@pytest.mark.parametrize("config_cls", CONFIG_CLASSES,
                         ids=lambda c: c.__name__)
def test_every_config_has_what_the_engine_reads_off_a_config(config_cls):
    reads = engine_reads("model_cfg")
    assert {"recurrent", "latent_pages", "window_pages", "wide_head_pages",
            "num_state_layers", "page_bytes", "state_bytes_per_slot",
            "window_page_bytes", "num_kv_heads", "vocab_size"} <= set(reads)
    cfg = config_cls()
    missing = [name for name in reads if not hasattr(cfg, name)]
    assert missing == []
    assert isinstance(cfg.page_bytes(8, 2, 1, 1), int)
    assert (cfg.state_bytes_per_slot() > 0) == bool(cfg.recurrent)
    assert (cfg.window_page_bytes(8, 2) > 0) == bool(cfg.window_pages)


@pytest.mark.parametrize(
    "model_cls", sorted({row[2] for row in MODEL_TYPES.values()},
                        key=lambda c: c.__name__), ids=lambda c: c.__name__)
def test_every_class_has_what_the_runner_reads_off_a_model(model_cls):
    reads = set(engine_reads("model"))
    assert {"AUX_NAMES", "SKIPS_CROSS_DECODER", "TOKEN_BUDGET", "forward",
            "init_params", "param_pspecs", "make_kv_cache",
            "cache_pspec"} <= reads
    # adapters and the embeddings path are Llama's alone (ROADMAP D6, R16)
    for name in reads - {"init_lora_bank", "lora_pspecs", "encode"}:
        assert hasattr(model_cls, name), name
    if model_cls.AUX_NAMES:
        assert callable(model_cls.step_aux)


@pytest.mark.parametrize("preset", sorted(set(WEIGHT_DIGESTS) - {
    "tiny-llama-debug", "tiny-ouro-debug"}))
def test_the_default_shardings_follow_the_trees_the_class_makes(preset):
    model = registry.model_for(PRESETS[preset])
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    specs = model.param_pspecs()
    assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    assert all(s == P() for s in jax.tree.leaves(specs, is_leaf=is_spec))
    cache = jax.eval_shape(lambda: model.make_kv_cache(4, 8))
    assert model.cache_pspec() == {k: P() for k in cache}
    assert model.step_aux({"aux": 7}) == 7
    assert cache["aux"].shape == (len(model.AUX_NAMES),)


def test_the_engine_probes_no_model_and_no_config():
    """The contract is ``models/base.py``: under ``engine/`` no ``getattr``
    or ``hasattr`` takes a model class or a model config, whose default
    would be a second statement of it (and a misspelt property a silent
    ``False``)."""
    probes = [
        f"{path.name}:{node.lineno}"
        for path, tree in _engine_trees() for node in ast.walk(tree)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
        and node.func.id in ("getattr", "hasattr") and node.args
        and _is(node.args[0], "model_cfg", "model")]
    assert probes == []
