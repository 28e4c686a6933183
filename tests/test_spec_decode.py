"""Speculative decoding (n-gram prompt lookup) correctness.

The exactness contract: with speculation on, greedy output must be
token-for-token IDENTICAL to the non-speculative engine — acceptance only
shortcuts steps the model would have taken anyway. Repetitive prompts force
high accept rates (the interesting path); random prompts force rejects and
the no-draft fallback.
"""

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.spec import count_accepted, propose_ngram


# ---------------------------------------------------------------------------
# Proposer unit tests (pure host logic)
# ---------------------------------------------------------------------------


def test_propose_ngram_finds_repeat():
    # ... 7 8 9 | 5 6 [7 8 9] -> last trigram recurs at the start; the
    # continuation after the earlier occurrence is drafted.
    ids = [7, 8, 9, 10, 11, 12, 5, 6, 7, 8, 9]
    assert propose_ngram(ids, k=3) == [10, 11, 12]


def test_propose_ngram_most_recent_occurrence_wins():
    ids = [1, 2, 50, 3, 1, 2, 60, 1, 2]
    # bigram (1,2) occurs at 0 (->50) and 4 (->60); most recent wins.
    assert propose_ngram(ids, k=1) == [60]


def test_propose_ngram_prefers_longer_match():
    ids = [5, 1, 2, 3, 70, 9, 2, 3, 80, 1, 2, 3]
    # trigram (1,2,3) matches at 1 (->70); bigram (2,3) also matches at 6
    # (->80) but the longer n-gram is preferred.
    assert propose_ngram(ids, k=1, max_n=3) == [70]


def test_propose_ngram_none_when_no_repeat():
    assert propose_ngram([1, 2, 3, 4, 5], k=3) is None


def test_propose_ngram_overlapping_occurrence():
    # The only earlier occurrence of the suffix overlaps it — still valid
    # (run-of-token tails like "7 7" must draft the continuation "7").
    assert propose_ngram([3, 7, 7], k=1) == [7]
    # Longest-n-gram match near the end: the continuation is truncated by
    # the sequence boundary (a 1-token draft, not None).
    assert propose_ngram([5, 5, 5, 5], k=2) == [5]


def test_count_accepted():
    # argmax rows: model emits 10, 11, 99 at positions 0, 1, 2.
    am = np.array([10, 11, 99, 7])
    assert count_accepted([10, 11, 12], am) == 2
    assert count_accepted([10, 11, 99], am) == 3
    assert count_accepted([4, 11, 99], am) == 0
    assert count_accepted([], am) == 0


# ---------------------------------------------------------------------------
# Engine exactness
# ---------------------------------------------------------------------------


def make_engine(**over):
    kw = dict(
        model="tiny-llama-debug",
        max_model_len=256,
        block_size=8,
        num_kv_blocks=128,
        max_num_seqs=8,
        max_prefill_tokens=64,
    )
    kw.update(over)
    return LLMEngine(EngineConfig(**kw))


def run_greedy(eng, rid, prompt, n, temperature=0.0, seed=0):
    eng.add_request(
        rid, prompt_token_ids=list(prompt),
        sampling=SamplingParams(
            max_tokens=n, temperature=temperature, seed=seed, ignore_eos=True
        ),
    )
    toks = []
    while eng.has_work():
        for out in eng.step():
            toks.extend(out.new_token_ids)
    return toks


# A prompt whose tail repeats an earlier span: greedy decode tends to keep
# reproducing seen continuations, so lookup drafts accept often.
REPEAT = [11, 22, 33, 44, 55, 66, 77, 88, 11, 22, 33, 44, 55, 66, 77, 88,
          11, 22, 33, 44]
RANDOM = [3, 17, 98, 255, 42, 7, 205, 131, 8, 77, 123, 9, 54, 201, 33, 4]


@pytest.mark.parametrize("prompt", [REPEAT, RANDOM])
def test_spec_greedy_output_identical(prompt):
    base = run_greedy(make_engine(), "b0", prompt, 24)
    spec_eng = make_engine(speculative_ngram=4)
    got = run_greedy(spec_eng, "s0", prompt, 24)
    assert got == base
    assert len(got) == 24


def test_spec_accepts_on_repetitive_prompt():
    eng = make_engine(speculative_ngram=4)
    run_greedy(eng, "s1", REPEAT, 24)
    assert eng.spec_proposed_total > 0
    # The repetitive prompt must actually shortcut steps, not just propose.
    assert eng.spec_accepted_total > 0
    s = eng.stats()
    assert s["spec_decode_num_accepted_tokens_total"] == float(
        eng.spec_accepted_total
    )


def test_spec_batch_of_sequences_identical():
    prompts = [REPEAT, RANDOM, REPEAT[4:], [9, 9, 9, 9, 9, 9, 9, 9, 9]]

    def run_all(**over):
        eng = make_engine(**over)
        for i, p in enumerate(prompts):
            eng.add_request(
                f"r{i}", prompt_token_ids=list(p),
                sampling=SamplingParams(
                    max_tokens=16, temperature=0.0, ignore_eos=True
                ),
            )
        outs = {f"r{i}": [] for i in range(len(prompts))}
        while eng.has_work():
            for out in eng.step():
                outs[out.request_id].extend(out.new_token_ids)
        return outs

    assert run_all(speculative_ngram=4) == run_all()


def test_spec_sampled_requests_bypass_speculation():
    """A lone temperature>0 request never triggers a verify pass (no
    draft-carrying rows) — and seeded sampling stays reproducible."""
    eng = make_engine(speculative_ngram=4)
    a = run_greedy(eng, "t0", REPEAT, 12, temperature=0.8, seed=7)
    assert eng.spec_proposed_total == 0
    eng2 = make_engine()
    b = run_greedy(eng2, "t1", REPEAT, 12, temperature=0.8, seed=7)
    assert a == b


def test_spec_mixed_greedy_and_sampled_batch_identical():
    """Sampled rows ride the verify step (position 0 fully sampled) while
    greedy rows speculate — both must match their solo non-spec runs."""
    def run_pair(spec: bool):
        eng = make_engine(**({"speculative_ngram": 4} if spec else {}))
        eng.add_request(
            "g", prompt_token_ids=list(REPEAT),
            sampling=SamplingParams(
                max_tokens=16, temperature=0.0, ignore_eos=True
            ),
        )
        eng.add_request(
            "s", prompt_token_ids=list(RANDOM),
            sampling=SamplingParams(
                max_tokens=16, temperature=0.9, seed=11, ignore_eos=True
            ),
        )
        outs = {"g": [], "s": []}
        while eng.has_work():
            for out in eng.step():
                outs[out.request_id].extend(out.new_token_ids)
        return outs, eng

    base, _ = run_pair(spec=False)
    spec, eng = run_pair(spec=True)
    assert spec == base
    assert eng.spec_proposed_total > 0  # the greedy row did speculate


def test_spec_respects_max_model_len():
    """Sequences close to max_model_len must not write KV past the last
    page (drafts are suppressed; output still exact)."""
    eng = make_engine(speculative_ngram=4, max_model_len=32)
    base = make_engine(max_model_len=32)
    p = REPEAT[:20]
    got = run_greedy(eng, "m0", p, 11)
    want = run_greedy(base, "m1", p, 11)
    assert got == want
    assert len(got) == 11  # 20 + 11 < 32 hard cap, engine-level len guard


def test_spec_with_lora_adapter_identical(tmp_path):
    """Verify must score drafts WITH the row's adapter: spec+LoRA output
    must equal non-spec LoRA output (and differ from the base model's)."""
    import json

    from safetensors.numpy import save_file

    from production_stack_tpu.models.registry import PRESETS

    mc = PRESETS["tiny-llama-debug"]
    rng = np.random.default_rng(7)
    d = tmp_path / "ad1"
    d.mkdir()
    (d / "adapter_config.json").write_text(json.dumps({
        "r": 4, "lora_alpha": 8.0,
        "target_modules": ["q_proj", "v_proj"], "peft_type": "LORA",
    }))
    tensors = {}
    for t, (din, dout) in (
        ("q_proj", (mc.hidden_size, mc.q_size)),
        ("v_proj", (mc.hidden_size, mc.kv_size)),
    ):
        for i in range(mc.num_layers):
            key = f"base_model.model.model.layers.{i}.self_attn.{t}"
            tensors[f"{key}.lora_A.weight"] = (
                rng.standard_normal((4, din)).astype(np.float32) * 0.3
            )
            tensors[f"{key}.lora_B.weight"] = (
                rng.standard_normal((dout, 4)).astype(np.float32) * 0.3
            )
    save_file(tensors, str(d / "adapter_model.safetensors"))

    def run(spec: bool):
        eng = make_engine(
            enable_lora=True, max_loras=2, max_lora_rank=8,
            lora_dir=str(tmp_path), attn_impl="gather",
            **({"speculative_ngram": 4} if spec else {}),
        )
        eng.load_lora("ad1", str(d))
        eng.add_request(
            "L0", prompt_token_ids=list(REPEAT),
            sampling=SamplingParams(
                max_tokens=16, temperature=0.0, ignore_eos=True
            ),
            lora_name="ad1",
        )
        toks = []
        while eng.has_work():
            for out in eng.step():
                toks.extend(out.new_token_ids)
        return toks, eng

    base_toks, _ = run(spec=False)
    spec_toks, eng = run(spec=True)
    assert spec_toks == base_toks
    assert eng.spec_proposed_total > 0  # speculation did engage for LoRA rows


def test_spec_with_prefix_cache_and_preemption_pressure():
    """Speculation composes with tight page budgets (preemption path)."""
    eng = make_engine(speculative_ngram=4, num_kv_blocks=24, max_num_seqs=4)
    base = make_engine(num_kv_blocks=24, max_num_seqs=4)
    outs, wants = {}, {}
    for i in range(3):
        outs[i] = run_greedy(eng, f"p{i}", REPEAT, 16)
        wants[i] = run_greedy(base, f"q{i}", REPEAT, 16)
    assert outs == wants


@pytest.mark.parametrize("top", [1, 5])
def test_a_row_with_logprobs_rides_the_verify_step_draftless(top):
    """One row of the batch asks for log-probabilities: the batch speculates
    all the same (before PR 53 it did not). That row rides the verify step
    without a draft, position 0's log-probabilities packed for it, and
    reports what the non-speculative engine reports; the greedy row beside
    it is drafted for."""
    def run_pair(spec: bool):
        eng = make_engine(**({"speculative_ngram": 4} if spec else {}))
        for rid, lp in (("g", None), ("l", top), ("h", None)):
            eng.add_request(
                rid, prompt_token_ids=list(REPEAT),
                sampling=SamplingParams(
                    max_tokens=16, temperature=0.0, ignore_eos=True,
                    logprobs=lp),
            )
        outs = {"g": [], "l": [], "h": []}
        while eng.has_work():
            for out in eng.step():
                lps = out.logprobs or [None] * len(out.new_token_ids)
                outs[out.request_id].extend(
                    (t, lp and (round(lp["logprob"], 3),
                                tuple(i for i, _ in lp["top"])))
                    for t, lp in zip(out.new_token_ids, lps))
        return outs, eng

    base, _ = run_pair(spec=False)
    spec, eng = run_pair(spec=True)
    assert spec == base
    assert all(lp is not None and len(lp[1]) == top for _, lp in spec["l"])
    assert all(lp is None for _, lp in spec["g"])
    assert eng.spec_proposed_total > 0 and eng.spec_accepted_total > 0
