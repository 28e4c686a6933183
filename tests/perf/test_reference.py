"""The plain reference (perf/reference) against the repository's independent
numpy oracle at a tiny size, the engine's weight recipe, and the flip-aware
comparison that decides ``correct``."""

import copy
import os

import numpy as np
import pytest

from perf import check, config as configs
from perf.reference import mistral
from perf.reference import model as ref
from perf.reference import weights
from tests import numpy_reference

DATA = os.path.join(os.path.dirname(__file__), "data")
TOKENS = [5, 77, 300, 41, 8, 210, 99, 3, 450, 17, 64, 128, 256, 9, 33, 501, 12, 70]


def _reference_logprobs(cfg, params, tokens, variant="none"):
    import jax.numpy as jnp

    hf = cfg.hf
    padded = ref.pad_len(len(tokens))
    ids = np.zeros(padded, np.int32)
    ids[: len(tokens)] = tokens
    final_norm, head = weights.head_weights(params)
    x = weights.embed_rows(params, jnp.asarray(ids))
    n_heads = hf["num_attention_heads"]
    theta = 1e4 if variant == "rope_1e4" else hf["rope_theta"]
    cos, sin = ref.rope_tables(padded, hf["hidden_size"] // n_heads, theta)
    gaps = np.full(padded, np.inf, np.float32)
    for li in range(hf["num_hidden_layers"]):
        x, gap = ref.layer(
            x, jnp.asarray(cos), jnp.asarray(sin), mistral.layer_weights(params, li),
            n_heads=n_heads, n_kv=hf["num_key_value_heads"],
            top_k=hf.get("num_experts_per_tok", 2), eps=hf["rms_norm_eps"],
            renorm=variant != "no_renorm")
        gaps = np.minimum(gaps, np.asarray(gap))
    lps = ref.head_logprobs(x[: len(tokens)], final_norm, head, eps=hf["rms_norm_eps"])
    return np.asarray(lps), gaps[: len(tokens)]


def _oracle_logprobs(cfg, params, tokens):
    mc = configs.program_model_config(cfg)
    tree = numpy_reference.dequant_tree(
        {k: ({n: np.asarray(w) for n, w in v.items()} if k == "layers" else np.asarray(v))
         for k, v in params.items()})
    logits = numpy_reference.ref_decoder_forward(mc, tree, tokens).astype(np.float64)
    logits -= logits.max(-1, keepdims=True)
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True))


@pytest.fixture(scope="module", params=["tiny-dense-int4", "tiny-moe"])
def built(request):
    cfg = configs.load(os.path.join(DATA, "configs", f"{request.param}.json"))
    return cfg, mistral.weights(cfg)


def test_reference_agrees_with_the_numpy_oracle(built):
    cfg, params = built
    got, _ = _reference_logprobs(cfg, params, TOKENS)
    want = _oracle_logprobs(cfg, params, TOKENS)
    assert np.abs(got - want).max() < 2e-4


def test_padding_at_the_end_changes_nothing(built):
    cfg, params = built
    a, _ = _reference_logprobs(cfg, params, TOKENS)
    b, _ = _reference_logprobs(cfg, params, TOKENS[:7])
    assert np.abs(a[:7] - b).max() < 1e-5


def test_weights_follow_the_engines_recipe(built):
    """The same arrays as ``ModelRunner`` holds for ``--seed weights_seed``."""
    cfg, params = built
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.models import registry

    registry.PRESETS[cfg.name] = configs.program_model_config(cfg)
    try:
        runner = ModelRunner(EngineConfig(
            model=cfg.name, seed=cfg.weights_seed, num_kv_blocks=8, block_size=16,
            max_model_len=64, quantization=cfg.flag("--quantization")))
    finally:
        registry.PRESETS.pop(cfg.name, None)
    theirs = runner.params
    assert set(theirs["layers"]) == set(params["layers"])
    for name, leaf in params["layers"].items():
        np.testing.assert_allclose(
            np.asarray(leaf, np.float32), np.asarray(theirs["layers"][name], np.float32),
            rtol=0, atol=1e-6 if leaf.dtype == np.float32 else 0, err_msg=name)
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(params[name], np.float32),
                                      np.asarray(theirs[name], np.float32))


def test_the_recipe_asks_the_model_object_for_its_keys_and_initialisers():
    """A model class with other quantised leaves carries its own key sets
    and initialisers (same names as ``models/llama.py``'s) on the object it
    hands in, all five, and they are followed; an object that carries none,
    as the program's ``Llama``, gets ``models/llama.py``'s; one that carries
    some and forgets others is refused, not silently given another model's."""
    import jax.numpy as jnp

    from production_stack_tpu.models import llama

    cfg = configs.load(os.path.join(DATA, "configs", "tiny-dense-int4.json"))
    inner = llama.Llama(configs.program_model_config(cfg))
    plain = weights.recipe_of(inner)
    assert all(getattr(plain, n) is getattr(llama, n) for n in weights.RECIPE)

    class Other:
        QUANT_LAYER_KEYS = ("wq", "w_down")  # wk, wv, wo, w_gate, w_up stay plain
        QUANT_TOP_KEYS = ("embed",)          # and so does lm_head
        init_params = inner.init_params

        @staticmethod
        def init_leaf(name, shape, dtype, key):
            if name == "wk":
                return jnp.full(shape, 0.5, dtype)
            return llama.init_leaf(name, shape, dtype, key)

    with pytest.raises(ValueError, match="quantize_leaf.*all or none"):
        weights.recipe_of(Other())  # three of the five
    Other.quantize_leaf = staticmethod(llama.quantize_leaf)
    Other.quantize_leaf_int4 = staticmethod(llama.quantize_leaf_int4)
    recipe = weights.recipe_of(Other())
    assert recipe.QUANT_LAYER_KEYS == ("wq", "w_down")
    assert recipe.init_leaf is Other.init_leaf
    assert recipe.quantize_leaf_int4 is llama.quantize_leaf_int4
    theirs = weights.engine_params(Other(), cfg.weights_seed, "int4")
    ours = weights.engine_params(inner, cfg.weights_seed, "int4")
    scales = {n for n in theirs["layers"] if n.endswith(("_q4s", "_qs"))}
    assert scales == {"wq_q4s", "w_down_q4s"}
    assert {n for n in ours["layers"] if n.endswith("_q4s")} == {
        n + "_q4s" for n in llama.QUANT_LAYER_KEYS}
    assert "embed_qs" in theirs and "lm_head_qs" not in theirs
    assert "lm_head_qs" in ours
    # its own initialiser made wk; the leaves both quantise are the same arrays
    assert np.all(np.asarray(theirs["layers"]["wk"], np.float32) == 0.5)
    assert theirs["layers"]["wk"].dtype == theirs["layers"]["wv"].dtype != np.int8
    for name in ("wq", "wq_q4s", "w_down", "w_down_q4s"):
        np.testing.assert_array_equal(np.asarray(theirs["layers"][name]),
                                      np.asarray(ours["layers"][name]))
    np.testing.assert_array_equal(np.asarray(theirs["embed"]), np.asarray(ours["embed"]))


def test_negative_controls_move_the_logprobs(built):
    cfg, params = built
    variant = "no_renorm" if "num_local_experts" in cfg.hf else "rope_1e4"
    good, _ = _reference_logprobs(cfg, params, TOKENS)
    bad, _ = _reference_logprobs(cfg, params, TOKENS, variant)
    assert np.abs(good - bad)[4:].max() > 0.05


# -- the comparison ---------------------------------------------------------


def _case(err_at=None, gap_at=None, n=16):
    """One checked sequence whose system logprobs equal the reference's,
    except ``err_at`` {pos: error}; ``gap_at`` {pos: router gap}."""
    sys_lp = [{t: -1.0 - t for t in range(5)} for _ in range(n)]
    ref_lp = [{str(t): v for t, v in at.items()} for at in copy.deepcopy(sys_lp)]
    for pos, e in (err_at or {}).items():
        sys_lp[pos][0] += e
    gaps = [1.0] * n
    for pos, g in (gap_at or {}).items():
        gaps[pos] = g
    parsed = [{"id": "s", "complete": True, "sys": sys_lp, "n_prompt": 4,
               "tokens": [], "want": []}]
    reference = [{"id": "s", "logprobs": ref_lp, "gap": gaps}]
    return parsed, reference


TH = {"delta": 0.05, "tau": 0.1, "tau_loose": 4.0}


def test_agreement_is_correct():
    assert check.compare(*_case(err_at={3: 0.02}), TH)["correct"]


def test_an_error_at_a_clear_position_fails():
    v = check.compare(*_case(err_at={3: 0.5}), TH)
    assert not v["correct"] and v["max_clear_err"] == pytest.approx(0.5)


def test_a_flip_at_a_near_tie_is_excluded():
    v = check.compare(*_case(err_at={3: 0.5}, gap_at={3: 0.01}), TH)
    assert v["correct"] and v["clear_share"] == pytest.approx(15 / 16)
    assert v["max_unclear_err"] == pytest.approx(0.5)


def test_garbage_at_a_near_tie_still_fails():
    assert not check.compare(*_case(err_at={3: 9.0}, gap_at={3: 0.01}), TH)["correct"]


def test_mostly_unclear_is_not_a_pass():
    gaps = {p: 0.01 for p in range(9)}
    assert not check.compare(*_case(gap_at=gaps), TH)["correct"]


def test_a_short_or_nonfinite_response_fails():
    parsed, reference = _case()
    parsed[0]["complete"] = False
    assert not check.compare(parsed, reference, TH)["correct"]
    body = {"choices": [{"logprobs": {
        "tokens": ["t5"] * 16, "token_logprobs": [float("nan")] * 16,
        "top_logprobs": [{f"t{i}": -1.0 for i in range(5)}] * 16}}]}
    assert not check.parse_response({"id": "s", "prompt": [1, 2]}, body)["complete"]


def test_failures_and_timing_do_not_enter_correct():
    v = check.compare(*_case(), TH)
    assert set(v) >= {"correct", "clear_share", "max_clear_err", "max_unclear_err"}
    assert "failed" not in v and "attempted" not in v


def test_a_share_of_clear_positions_may_flip_where_the_configuration_says_so():
    th = dict(TH, clear_within_min=0.7, tau_median=0.05)
    flips = {p: 1.5 for p in (2, 9, 13)}  # 3 of 16 beyond tau: 0.81 within
    v = check.compare(*_case(err_at=flips), th)
    assert v["correct"] and v["clear_within_tau"] == pytest.approx(13 / 16)
    assert not check.compare(*_case(err_at=flips), TH)["correct"]  # default: every one
    many = {p: 1.5 for p in range(6)}  # 10 of 16 within: under 0.7
    assert not check.compare(*_case(err_at=many), th)["correct"]
    shifted = {p: 0.08 for p in range(16)}  # all within tau, but the bulk moved
    assert not check.compare(*_case(err_at=shifted), th)["correct"]
    assert check.compare(*_case(err_at=shifted), dict(th, tau_median=None))["correct"]
    garbage = {4: 9.0}
    assert not check.compare(*_case(err_at=garbage), th)["correct"]
