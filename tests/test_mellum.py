"""The window / full attention mix (``models/mellum.py``: periods of window
layers closed by a full layer under YaRN, every layer a softmax-routed
expert block over an expert-parallel share) on the engine's normal path,
against the benchmark's plain reference (``perf/reference/mellum.py``:
float32, attention over the whole sequence, nothing of the program's forward
pass), at tiny widths: two periods of three window layers (16 tokens) and
one full layer, hidden 64, 8 experts top 2, pages of 8.

What the benchmark's ``correct`` cannot see is here: rows against each other,
the two page groups against one cache-free run (a session's later turns
through the prefix cache of both groups, a shared prefix whose window pages
its first owner trimmed, a match cut back where the window group has lost
its pages), the shares against the whole.
"""

import dataclasses
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import mellum as reference
from production_stack_tpu.engine import config as engine_config
from production_stack_tpu.engine.config import EngineConfig, window_block_count
from production_stack_tpu.engine.kv_manager import BlockAllocator
from production_stack_tpu.engine.sequence import SamplingParams, Sequence
from production_stack_tpu.models import base
from production_stack_tpu.models.mellum import Mellum, MellumConfig
from production_stack_tpu.models.registry import PRESETS

from . import model_contract as contract
from .model_contract import assert_same, run

NAME = "tiny-mellum-debug"
CFG = PRESETS[NAME]
YARN = {"rope_type": "yarn", "rope_theta": CFG.full_rope_theta,
        "factor": CFG.yarn_factor,
        "original_max_position_embeddings": CFG.yarn_original_max_position,
        "beta_fast": CFG.yarn_beta_fast, "beta_slow": CFG.yarn_beta_slow,
        "attention_factor": CFG.yarn_attention_factor}
HF = {"num_hidden_layers": CFG.num_layers,
      "hidden_size": CFG.hidden_size,
      "layer_types": list(CFG.layer_types),
      "sliding_window": CFG.sliding_window,
      "num_attention_heads": CFG.num_heads,
      "num_key_value_heads": CFG.num_kv_heads,
      "head_dim": CFG.head_dim,
      "rope_parameters": {
          "sliding_attention": {"rope_type": "default",
                                "rope_theta": CFG.rope_theta},
          "full_attention": YARN},
      "num_experts": CFG.n_routed_experts,
      "num_experts_per_tok": CFG.num_experts_per_tok,
      "norm_topk_prob": CFG.norm_topk_prob,
      "rms_norm_eps": CFG.rms_norm_eps,
      "ep_share": {"first": CFG.expert_first, "of": CFG.router_experts}}
REF_CFG = types.SimpleNamespace(
    hf=HF, raw={"published": {"num_experts": CFG.router_experts}})
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]
OTHER = [(7 * i + 3) % 127 + 1 for i in range(120)]

make_engine = functools.partial(contract.make_engine, NAME)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def uncached():
    """The same engine with no prefix cache: what every hit must equal."""
    return make_engine(enable_prefix_caching=False)


@pytest.fixture(scope="module")
def params(engine):
    return engine.runner.params


def reference_logprobs(params, ids, n_prompt, n_gen, variant="none"):
    with jax.default_matmul_precision("highest"):
        (lps, gap), = reference.teacher_force(
            REF_CFG, params,
            [{"tokens": list(ids), "n_prompt": n_prompt,
              "want": [[0]] * n_gen}], variant)
    assert gap.shape == (n_gen,) and np.all(gap >= 0)
    return lps


assert_matches_reference = functools.partial(
    contract.assert_matches_reference,
    lambda params, prompt, tokens: reference_logprobs(
        params, prompt + tokens, len(prompt), len(tokens)))


# ----------------------------------------------------------------------------
# The engine's normal path against the reference's full forward pass
# ----------------------------------------------------------------------------


def test_chunked_prefill_then_decode_through_both_groups(engine, params):
    """53 prompt tokens (more than three windows) in chunks of 16, then
    chained decode: the window layers read their own group through its
    table while its pages below the window go, the full layers the global
    group; every reported log-probability is the reference's."""
    seen = {"held": 0}

    def watch(seq):
        seen["held"] = max(
            seen["held"], len(seq.window_block_ids) - seq.window_released)

    got = run(engine, [PROMPT], 8, watch=watch)[0]
    assert len(got["tokens"]) == 8
    assert_matches_reference(params, PROMPT, got)
    assert engine.pipelined_bursts_total > 0, "decode must run chained"
    assert 0 < seen["held"] <= engine.allocator.window_bound(16)
    stats = engine.stats()
    assert stats["window_pages_released_total"] >= 4
    assert stats["window_pages_in_use"] == 0 and stats["kv_pages_in_use"] == 0
    assert stats["window_pages_cached"] > 0  # kept under their hashes
    assert (stats["window_page_steps_total"]
            < stats["window_whole_context_page_steps_total"])
    # the dispatch's five counts: every real token routes top_k pairs in
    # each of the eight layers, and this share holds every expert
    assert stats["moe_layer_steps_total"] % CFG.num_layers == 0
    assert stats["moe_pairs_held_total"] == stats["moe_pairs_routed_total"] > 0


def test_the_server_exports_the_dispatch_counts(engine):
    """``pst:moe_dispatch_overflow_total`` beside the accepted five."""
    run(engine, [PROMPT[:20]], 2)
    contract.assert_dispatch_counts_exported(engine)


def test_a_prompt_cut_into_three_chunks_equals_one_chunk(uncached, params):
    prompt = PROMPT[:48]
    three = run(uncached, [prompt], 4)[0]  # the defaults: chunks of 16
    one = run(make_engine(max_prefill_tokens=64, overlap_decode=False,
                          enable_prefix_caching=False), [prompt], 4)[0]
    assert_same(three, one)
    assert_matches_reference(params, prompt, one)


def test_four_ragged_packed_rows_equal_four_lone_rows(params):
    """Four sequences of different lengths arrive together: packed and
    padded prefill steps, each row through its own tables of both groups."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18)]
    kw = dict(max_prefill_tokens=32, enable_prefix_caching=False)
    together = run(make_engine(**kw), prompts, 6)
    lone = make_engine(**kw)
    for p, got in zip(prompts, together):
        assert_same(got, run(lone, [p], 6)[0])
        assert_matches_reference(params, p, got)


def test_a_staggered_many_row_run_equals_the_synchronous_loop(params):
    """Eight sequences arrive three steps apart under a chain of four rows:
    each joins behind its own prefill with no drain while the chain runs
    on; its window table joins with it. Tokens and log-probabilities are
    the synchronous loop's, request by request."""
    prompts = [(PROMPT + OTHER)[i:i + n] for i, n in enumerate(
        (37, 5, 53, 18, 26, 11, 44, 9))]
    kw = dict(max_num_seqs=4, min_decode_bucket=4, max_prefill_tokens=32)
    sync = run(make_engine(overlap_decode=False, **kw), prompts, 9, stagger=3)
    eng = make_engine(**kw)
    got = run(eng, prompts, 9, stagger=3)
    for a, b in zip(got, sync):
        assert_same(a, b)
    assert eng.chain_kept_prefills_total >= 7
    assert eng.pipeline_breaks["prefill"] == 0
    assert eng.allocator.window_pages_in_use == 0
    assert eng.allocator.num_free == eng.allocator.num_blocks
    assert_matches_reference(params, prompts[2], got[2])


def test_preemption_by_recompute_returns_the_same_tokens(uncached):
    """Twelve global pages: two 40-token prompts admit and one must lose
    its pages of both groups while decoding; it starts again."""
    p1, p2 = PROMPT[:40], OTHER[:40]
    tight = make_engine(num_kv_blocks=12, max_model_len=128)
    got = run(tight, [p1, p2], 10)
    assert tight.num_preempted_total > 0, "the test must exercise preemption"
    for p, a in zip((p1, p2), got):
        assert a["tokens"] == run(uncached, [p], 10)[0]["tokens"]
    assert tight.allocator.window_pages_in_use == 0


# ----------------------------------------------------------------------------
# The prefix cache over both groups
# ----------------------------------------------------------------------------


def test_a_sessions_later_turns_come_from_the_cache_of_both_groups(
        uncached, params):
    """A session's second and third turn (everything so far, then a
    question) hit the prefix cache through both groups: an absorbed chunk
    over the cached context, then decode. The logits are those of the same
    turns with no prefix cache, and the reference's."""
    eng = make_engine()
    context, cached = list(PROMPT), []
    for question in ([], OTHER[:9], OTHER[20:33]):
        context += question
        got = run(eng, [context], 8)[0]
        cached.append(got["seq"].num_cached_prompt_tokens)
        assert_same(got, run(uncached, [context], 8)[0])
        context += got["tokens"]
    assert_matches_reference(params, context[:-8], got)
    # turn 2 finds turn 1's 53 + 7 computed tokens' whole pages, turn 3
    # turn 2's: all but the last page of what was computed
    assert cached == [0, 56, 72]
    stats = eng.stats()
    assert stats["window_prefix_tokens_lost_total"] == 0
    assert stats["prefix_cache_hits_total"] == 56 + 72
    assert eng.allocator.window_pages_in_use == 0


def test_a_shared_prefix_hits_after_its_first_owner_trimmed_it(uncached):
    """The first owner of a 24-token shared prefix moved four windows past
    it and gave its window pages up long ago; they wait in the group's LRU
    and a second prompt with the same prefix takes them again."""
    eng = make_engine()
    shared = PROMPT[:24]
    first = run(eng, [shared + OTHER[:45]], 6)[0]
    assert first["seq"].num_cached_prompt_tokens == 0
    assert eng.allocator.window_pages_released >= 7
    prompt = shared + OTHER[60:77]
    got = run(eng, [prompt], 6)[0]
    assert got["seq"].num_cached_prompt_tokens == 24
    assert eng.allocator.window_prefix_tokens_lost == 0
    assert_same(got, run(uncached, [prompt], 6)[0])


def test_an_evicted_window_page_cuts_the_match_and_is_counted(uncached):
    """Sixteen other conversations end in the window group (28 pages, two
    under each one's last window) until the first session's hashed pages
    there are evicted; the global group (96 pages) still holds its prefix.
    Its next turn is matched in the global group, cut back to nothing by the
    window group, and computed from the start: the same logits."""
    eng = make_engine()
    first = run(eng, [PROMPT], 8)[0]
    for at in range(0, 96, 6):
        run(eng, [OTHER[at:at + 30]], 2)
    assert eng.allocator.window_pages_evicted > 0
    turn = PROMPT + first["tokens"] + OTHER[100:109]
    got = run(eng, [turn], 8)[0]
    assert got["seq"].num_cached_prompt_tokens == 0
    stats = eng.stats()
    assert stats["window_prefix_tokens_lost_total"] == 56
    assert stats["prefix_cache_hits_total"] == 0
    assert_same(got, run(uncached, [turn], 8)[0])


def _sequence(tokens):
    return Sequence("s", list(tokens), SamplingParams(max_tokens=1))


def _compute(alloc, seq, chunk=16):
    """What the scheduler and the engine do to a sequence's pages while its
    prompt is computed in chunks."""
    while seq.num_computed_tokens < seq.num_prompt_tokens:
        end = min(seq.num_computed_tokens + chunk, seq.num_prompt_tokens)
        for _ in range(seq.blocks_needed(end, alloc.block_size)):
            seq.block_ids.append(alloc.allocate())
        alloc.advance_window(seq, end)
        seq.num_computed_tokens = end
        seq.commit_full_blocks(alloc)
        alloc.trim_window(seq)


def _match(alloc, seq):
    blocks, hashes = alloc.match_prefix(seq.all_token_ids[:-1])
    return alloc.match_window(seq, blocks, hashes)


def test_a_match_is_cut_back_to_where_both_groups_still_cover():
    """Pages of 8, a window of 16. A computed 64 tokens and left; two pages
    of the window group are evicted (5 and 4: the pages it passed go first,
    the last passed before the others): a 64-token match of the global group
    still ends at 64, since the window group holds pages 6 and 7. B, sharing
    A's first 32 tokens, takes pages 2 and 3 again and gives them back: the
    LRU's youngest. Four more evictions take pages 1, 0, 6 and 7, and the
    next 64-token match is cut back to 32, where pages 2 and 3 cover the
    last window: 32 tokens lost."""
    alloc = BlockAllocator(64, 8, True, window_blocks=12, window_tokens=16)
    a = _sequence(range(64))
    _compute(alloc, a)
    assert a.window_released == 6 and alloc.window_pages_cached == 6
    alloc.release_sequence(a)
    assert alloc.window_pages_in_use == 0 and alloc.window_pages_cached == 8
    taken = [alloc.window.allocate() for _ in range(4 + 2)]  # 4 were free
    assert alloc.window_pages_evicted == 2
    again = _sequence(list(range(64)) + [1])
    blocks, hashes = _match(alloc, again)
    assert len(blocks) == len(hashes) == 8 and again.window_released == 6
    assert again.window_block_ids[:6] == [0] * 6
    assert alloc.window_pages_in_use == 6 + 2
    assert alloc.window_prefix_tokens_lost == 0 and alloc.hit_tokens == 64
    again.block_ids = list(blocks)
    alloc.release_sequence(again)
    b = _sequence(list(range(32)) + list(range(100, 117)))
    blocks, _ = _match(alloc, b)
    assert len(blocks) == 4 and b.window_block_ids[:2] == [0, 0]
    assert b.window_released == 2 and len(b.window_block_ids) == 4
    b.block_ids = list(blocks)
    alloc.release_sequence(b)
    taken += [alloc.window.allocate() for _ in range(4)]
    assert alloc.window_pages_evicted == 6
    again = _sequence(list(range(64)) + [1])
    blocks, hashes = _match(alloc, again)
    assert len(blocks) == len(hashes) == 4
    assert again.window_block_ids[:2] == [0, 0] and again.window_released == 2
    assert alloc.window_prefix_tokens_lost == 32
    assert alloc.hit_tokens == 64 + 32 + 32
    # the global pages past the cut went back: all 8 are cached, 4 referenced
    assert alloc.num_free == 64 - 4
    again.block_ids = list(blocks)
    alloc.release_sequence(again)
    alloc.window.release_all(taken)
    assert alloc.window_pages_in_use == 0 and alloc.num_free == 64


def test_pages_a_sequence_only_passed_go_before_any_conversations_last_window():
    """Pages of 8, a window of 16, 16 pages. Three conversations of 40
    tokens wait for their next turn: the two pages under each one's last
    window are the LRU's. A prompt of 200 tokens passes through: the pages
    it passes are the first to go, its own among them, and every waiting
    conversation is still matched whole."""
    alloc = BlockAllocator(128, 8, True, window_blocks=16, window_tokens=16)
    waiting = [[100 * i + t for t in range(40)] for i in range(1, 4)]
    for tokens in waiting:
        seq = _sequence(tokens + [1])
        _compute(alloc, seq)
        alloc.release_sequence(seq)
    assert alloc.window_pages_cached == 3 * 5  # 3 passed and 2 kept, each
    long = _sequence(range(1000, 1200))
    _compute(alloc, long)
    alloc.release_sequence(long)
    assert alloc.window_pages_evicted >= 25 - 16
    for tokens in waiting:
        seq = _sequence(tokens + [1, 2])
        blocks, _ = _match(alloc, seq)
        assert len(blocks) == 5 and alloc.window_prefix_tokens_lost == 0
        seq.block_ids = list(blocks)
        alloc.release_sequence(seq)


def test_a_parting_point_computed_again_keeps_its_place():
    """A conversation of 40 tokens whose window pages went while the global
    group kept it: its next turn is cut back to nothing and computes the 40
    again. The pages under the last window before the point where it left
    the cached chain (3 and 4) keep an LRU page's place although it computed
    them itself; the others it passes go first. So the turn after, which
    parts at the same point, is matched."""
    alloc = BlockAllocator(128, 8, True, window_blocks=16, window_tokens=16)
    first = list(range(40))
    seq = _sequence(first + [1])
    _compute(alloc, seq)
    alloc.release_sequence(seq)
    taken = [alloc.window.allocate() for _ in range(16)]  # all of the group
    alloc.window.release_all(taken)
    assert alloc.window_pages_evicted == 5 and alloc.window_pages_cached == 0
    turn = _sequence(first + [7] * 30 + [1])
    assert _match(alloc, turn) == ([], [])
    assert alloc.window_prefix_tokens_lost == 40 and turn.window_parted == 5
    _compute(alloc, turn)  # 71 tokens: passes pages 0 to 5
    assert turn.window_released == 6
    order = list(alloc.window._reusable)
    ids = {alloc.window._block_of_hash[h]: i
           for i, h in enumerate(turn.block_hashes)}
    assert [ids[b] for b in order] == [5, 2, 1, 0, 3, 4]
    alloc.release_sequence(turn)
    taken = [alloc.window.allocate() for _ in range(8 + 4)]  # 8 were free
    again = _sequence(first + [8] * 9)
    blocks, _ = _match(alloc, again)
    assert len(blocks) == 5 and alloc.window_prefix_tokens_lost == 40
    assert again.window_block_ids[:3] == [0, 0, 0]


def test_with_prefix_caching_off_the_window_group_keeps_no_hash():
    """What the recurrent class with a window group runs (its prefix cache
    stays refused): a dropped page is free at once, nothing is matched."""
    alloc = BlockAllocator(64, 8, False, window_blocks=12, window_tokens=16)
    a = _sequence(range(64))
    _compute(alloc, a)
    assert alloc.window_pages_cached == 0 and alloc.window_pages_in_use == 2
    assert alloc.window_pages_released == 6
    alloc.release_sequence(a)
    assert sorted(alloc.window._free) == list(range(12))
    assert _match(alloc, _sequence(range(65))) == ([], [])


def test_the_window_group_is_sized_from_the_model_and_the_engines_limits(engine):
    cfg = EngineConfig(model=NAME, block_size=8, max_num_seqs=4,
                       max_prefill_tokens=16)
    # a row's residency, a waiting conversation's last window a row, and
    # the chunks in flight
    assert window_block_count(cfg, CFG) == 4 * (2 + 2 + 2) + 2 * 2
    cfg.enable_prefix_caching = False  # nothing waits to be matched
    assert window_block_count(cfg, CFG) == 4 * (2 + 2) + 2 * 2
    assert engine.runner.window_blocks == 28
    assert engine.runner.kv_cache["kv"].shape[:2] == (2, 96)
    assert engine.runner.kv_cache["wkv"].shape[:2] == (6, 28)


# ----------------------------------------------------------------------------
# The reference's controls, the rotary embedding, the shares
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("variant", reference.VARIANTS[1:])
def test_every_negative_control_moves_the_reference(variant, params):
    ids = PROMPT + PROMPT[:11]
    sound = reference_logprobs(params, ids, len(PROMPT), 12)
    broken = reference_logprobs(params, ids, len(PROMPT), 12, variant)
    moved = np.abs(sound - broken).max()
    # the precision controls move little at these widths, the equations much
    assert moved > (1e-4 if variant.endswith("_fp8") else 1e-2), moved


def test_yarn_frequencies_are_the_published_blend():
    """At the published sizes: the fastest lanes keep the default
    frequency, the slowest take a sixteenth, the ramp lies between the
    dimensions that turn 32 times and once in 8,192 positions; the
    program's and the reference's tables are the same numbers."""
    cfg = MellumConfig()
    inv, scale = cfg.inv_freq("full_attention")
    plain, one = cfg.inv_freq("sliding_attention")
    assert one == 1.0 and scale == 1.2772588722239782
    np.testing.assert_allclose(plain, 500000.0 ** (-np.arange(64) / 64))
    # turning(32) = 18.1 -> 18, turning(1) = 34.99 -> 35: lanes up to 18 as
    # published, lanes from 35 on divided by 16, a straight ramp between
    np.testing.assert_allclose(inv[:19], plain[:19])
    np.testing.assert_allclose(inv[35:], plain[35:] / 16)
    assert np.all(np.diff(inv / plain) <= 0)
    np.testing.assert_allclose((inv / plain)[27], 1 - 9 / 17 * 15 / 16)
    ref, ref_scale = reference.inv_freq(
        {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
         "original_max_position_embeddings": 8192, "beta_fast": 32,
         "beta_slow": 1, "attention_factor": 1.2772588722239782}, 128)
    np.testing.assert_array_equal(ref, inv)
    assert ref_scale == scale
    np.testing.assert_array_equal(
        base.yarn_inv_freq(16, 10000.0, 4.0, 64),
        CFG.inv_freq("full_attention")[0])


def test_yarn_frequencies_are_the_modelling_librarys():
    torch = pytest.importorskip("torch")
    from transformers import PretrainedConfig
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    hf = PretrainedConfig(
        rope_theta=500000.0, head_dim=128, hidden_size=2304,
        num_attention_heads=32, max_position_embeddings=131072,
        rope_scaling={"rope_type": "yarn", "factor": 16.0,
                      "original_max_position_embeddings": 8192,
                      "beta_fast": 32, "beta_slow": 1,
                      "attention_factor": 1.2772588722239782})
    inv, scale = _compute_yarn_parameters(hf, torch.device("cpu"))
    ours, ours_scale = MellumConfig().inv_freq("full_attention")
    np.testing.assert_allclose(ours, inv.numpy(), rtol=2e-6)
    assert ours_scale == scale


# 23 tokens: one row tile, the plain program; 200: a capacity, rounds traced
@pytest.mark.parametrize("tokens", [23, 200])
def test_the_four_shares_routed_parts_are_the_whole(tokens, params):
    """Four ranks of 4 of 16 experts, one router: the routed parts add up
    to the uncut reference's expert block."""
    E, held = 16, 4
    cfg = dataclasses.replace(CFG, router_experts=E, n_routed_experts=held)
    D, Fe = cfg.hidden_size, cfg.moe_intermediate_size
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, D))
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    whole = {"w1": jax.random.normal(ks[0], (E, D, 2 * Fe)) / np.sqrt(D),
             "w2": jax.random.normal(ks[1], (E, Fe, D)) / np.sqrt(Fe)}
    mp = {"norm": params["layers"]["moe"]["norm"][0],
          "w_router": jax.random.normal(ks[2], (D, E)) / np.sqrt(D)}
    u = reference._rms(x, mp["norm"], cfg.rms_norm_eps)
    valid = jnp.ones((tokens,), bool)
    total = 0.0
    for first in range(0, E, held):
        model = Mellum(dataclasses.replace(cfg, expert_first=first))
        banks = {k: v[first:first + held] for k, v in whole.items()}
        part, stats = model.routed(mp, banks, 0, u, valid)
        assert stats[0] == tokens * cfg.num_experts_per_tok
        assert 0 < stats[1] < stats[0]
        assert stats[5] == 0  # no share passed its capacity
        total = total + part
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(
            x, {**mp, **whole}, top_k=cfg.num_experts_per_tok, first=0,
            renorm=True, eps=cfg.rms_norm_eps, softmax=True)
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)


# ----------------------------------------------------------------------------
# The doors: flags, the configuration's keys, the pools
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("over,flag", [
    (dict(kv_swap=True), "--kv-swap"),
    (dict(cpu_offload_blocks=8), "--cpu-offload-blocks"),
    (dict(remote_kv_url="http://x"), "--remote-kv-url"),
    (dict(kv_role="producer"), "--kv-role"),
    (dict(speculative_ngram=3), "--speculative-ngram"),
    (dict(enable_lora=True), "--enable-lora"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(pipeline_parallel_size=2), "--pipeline-parallel-size"),
    (dict(expert_parallel_size=2), "--expert-parallel-size"),
    (dict(data_parallel_size=2), "--data-parallel-size"),
    (dict(quantization="int8"), "--quantization"),
    (dict(kv_cache_dtype="float8_e4m3fn"), "--kv-cache-dtype"),
])
def test_refused_at_start_up_by_the_flags_name(over, flag):
    """Every refusal of the window group, from the one table, with its
    sentence; prefix caching, the default, is served."""
    kw = dict(model=NAME, kv_swap=False)
    kw.update(over)
    with pytest.raises(ValueError) as e:
        engine_config.refuse_unserved(EngineConfig(**kw), CFG)
    (why,) = [w for on, f, w in engine_config._refusals(EngineConfig(**kw))
              if f == flag]
    assert str(e.value) == (
        f"{flag} is not served for model {NAME!r}, which "
        f"{engine_config._HAS['window_pages']}: {why['window_pages']}")
    served = EngineConfig(model=NAME, kv_swap=False)
    assert served.enable_prefix_caching
    engine_config.refuse_unserved(served, CFG)
    # the recurrent class with a window group keeps its refusal
    with pytest.raises(ValueError, match="--enable-prefix-caching"):
        engine_config.refuse_unserved(served, PRESETS["tiny-phi4flash-debug"])


def test_config_door_knows_the_model_type_and_the_arithmetic(tmp_path):
    from production_stack_tpu.models.llama import config_from_hf_json

    with open("perf/configs/mellum2-ep4-cut.json") as f:
        raw = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path), name="x")
    assert isinstance(cfg, MellumConfig) and cfg.window_pages
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (28, 2304, 24576)
    assert (cfg.period, cfg.periods, cfg.num_kv_layers,
            cfg.num_window_layers) == (4, 7, 7, 21)
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.expert_first,
            cfg.num_experts_per_tok) == (16, 64, 0, 8)
    assert (cfg.sliding_window, cfg.q_size, cfg.kv_size) == (1024, 4096, 512)
    assert (cfg.yarn_factor, cfg.yarn_original_max_position,
            cfg.yarn_attention_factor) == (16.0, 8192, 1.2772588722239782)
    # a token: 2,048 B a layer; a 128-token page 1.75 MiB over the 7 full
    # layers and 5.25 MiB over the 21 window layers
    assert cfg.page_bytes(128, 2) == 7 * 2048 * 128 == 1835008
    assert cfg.window_page_bytes(128, 2) == 21 * 2048 * 128 == 5505024
    eng = EngineConfig(model="x", block_size=128, max_num_seqs=32,
                       max_prefill_tokens=1024)
    assert window_block_count(eng, cfg) == 32 * (8 + 2 + 8) + 2 * 8 == 592
    shapes = jax.eval_shape(Mellum(cfg).init_params, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    attn, moe = (shapes["layers"][k] for k in ("attn", "moe"))
    assert round(count(attn) / 28 / 1e6, 2) == 21.24
    banks = count(moe["w1"]) + count(moe["w2"])
    assert round(banks / 28 / 1e6, 1) == 99.1
    assert round((count(moe) - banks) / 28 / 1e6, 2) == 0.15
    assert round((count(shapes["embed"]) + count(shapes["lm_head"])) / 1e6) == 113
    assert 3.480e9 < count(shapes) < 3.492e9  # 6.97 GB at 2 B a parameter
    # the whole model by the same count: 12.15 B
    whole = {**raw, **raw["published"]}
    whole.pop("ep_share")
    path.write_text(json.dumps(whole))
    full = config_from_hf_json(str(path))
    n = count(jax.eval_shape(Mellum(full).init_params, jax.random.PRNGKey(0)))
    assert 12.13e9 < n < 12.17e9
    for key, value, match in (
            ("num_hidden_layers", 26, "whole periods"),
            ("attention_bias", True, "attention_bias"),
            ("mlp_layer_types", ["dense"] * 28, "dense MLP"),
            ("use_sliding_window", False, "sliding_window")):
        path.write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=match):
            config_from_hf_json(str(path))
    bad = json.loads(json.dumps(raw))
    bad["rope_parameters"]["full_attention"]["rope_type"] = "longrope"
    path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="default or yarn"):
        config_from_hf_json(str(path))


def test_explicit_pages_are_checked_against_what_the_window_group_leaves(
        monkeypatch):
    """On a chip an explicit --num-kv-blocks that cannot fit beside the
    weights and the window group is an error that names them; with none the
    global group takes what the window group leaves."""
    from production_stack_tpu.models.mellum import config_from_hf

    with open("perf/configs/mellum2-ep4-cut.json") as f:
        cfg = config_from_hf(json.load(f), "x")
    dev = types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"bytes_limit": 16_909_336_064})
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    eng = EngineConfig(model="x", block_size=128, max_num_seqs=32,
                       max_prefill_tokens=1024, num_kv_blocks=2560)
    weights = 6_972_000_000
    assert engine_config.resolve_num_kv_blocks(eng, cfg, weights) == 2560
    eng.num_kv_blocks = 8192
    with pytest.raises(ValueError, match="--num-kv-blocks 8192"):
        engine_config.resolve_num_kv_blocks(eng, cfg, weights)
    eng.num_kv_blocks = None
    n = engine_config.resolve_num_kv_blocks(eng, cfg, weights)
    left = int(16_909_336_064 * 0.9) - weights - 592 * 5505024
    assert n == left // 1835008
