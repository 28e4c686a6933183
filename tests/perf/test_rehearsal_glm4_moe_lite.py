"""The latent-attention mixture of experts (``models/glm4_moe_lite.py``)
through the whole sequence of ``perf/run.py`` on the CPU at a tiny size: its
configuration (``"reference": "glm4_moe_lite"``, prefix caching on over
latent pages), a tiny closed-loop ``sessions`` mix and a benchmark file of
its own (``data/BENCHMARK.glm-tiny.json``: the accepted generic metrics and
this PR's, listed for the tiny cell), all found by name. And the cost
modules of its two kernels. Nothing here is a device number."""

import json
import os
import time

import pytest

from perf import config as configs
from perf import cost as costs
from perf import manifest, run

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
WINDOW_S = 10.0
CELL = "glm-tiny.glm-tiny-docqa"
PUBLISHED = {"num_attention_heads": 20, "num_hidden_layers": 8,
             "kv_lora_rank": 512, "qk_rope_head_dim": 64}


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.glm-tiny.json"))


def test_latent_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/glm4_moe_lite.py`` with a
    session turn through cached latent pages in the check set, the generic
    metrics read, the expert layer's counters read, the prefix cache hit."""
    line = json.loads(json.dumps(run.run_cell(
        CELL, 2**31 + 3333, WINDOW_S, True, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", CELL)}
    assert set(got) <= owed
    assert not {"kernel.mla_decode_roofline",
                "kernel.moe_experts_gated_roofline"} & set(got)
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.compiles_in_window", "runner.chained_decode_share",
            "sched.cached_prompt_share", "startup.history_prefill_s",
            "moe.experts_touched_share",
            "moe.gated_busiest_expert_over_mean"} <= set(got)
    # a turn finds its session's whole pages in the prefix cache
    assert got["sched.cached_prompt_share"] > 50
    # 8 experts, top 2, 1-3 rows a step: a step touches some, never all 64
    assert 100 * 2 / 64 <= got["moe.experts_touched_share"] <= 100 * 8 / 64
    assert got["moe.gated_busiest_expert_over_mean"] >= 1.0
    with open(os.path.join(tmp_path, "reference.log")) as f:
        assert "[reference] glm4_moe_lite: weights ready" in f.read()
    with open(os.path.join(tmp_path, "reference_result.json")) as f:
        rows = json.load(f)["variants"]["none"]
    assert len(rows) == 3 and all(
        len(r["gap"]) == 16 and all(0 <= g < 1 for g in r["gap"]) for r in rows)


def test_negative_controls_move_the_reference():
    """Every listed variant changes the log-probabilities of the tiny
    model."""
    import numpy as np

    from perf.reference import glm4_moe_lite as ref

    cfg = configs.load(os.path.join(DATA, "configs", "glm-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(0)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 40)],
             "n_prompt": 30, "want": [[1]] * 10}]
    base, gap = ref.teacher_force(cfg, params, seqs, "none")[0]
    assert base.shape == (10, 128) and gap.shape == (10,) and (gap >= 0).all()
    assert ref.VARIANTS[0] == "none"
    moved = {}
    for v in ref.VARIANTS[1:]:
        other, _ = ref.teacher_force(cfg, params, seqs, v)[0]
        moved[v] = float(np.abs(other - base).max())
    # none is a no-op, and at 40 positions of context the cache's precision
    # is as visible as the equations'
    assert all(m > 0.05 for m in moved.values()), moved


def test_reference_pads_to_whole_query_blocks_up_to_its_limit():
    from perf.reference import glm4_moe_lite as ref

    assert [ref._pad_len(n) for n in (1, 256, 257, 1024, 1025, 45000, 65536)] == [
        256, 256, 512, 1024, 2048, 45056, 65536]
    with pytest.raises(ValueError, match="beyond"):
        ref._pad_len(65537)


def test_mla_decode_cost_reads_each_latent_row_once():
    """12 documents of 16-41k tokens in 16 rows at the published widths:
    576 stored elements a token and layer (not 640 lanes, not a K and a V
    half), queries in and weighted latents out; memory decides."""
    cost = costs.load("mla_decode")
    step = {"rows": 12, "new_tokens": 12, "kv_tokens": 338_691}
    c = cost.cost(step, PUBLISHED, None)
    assert c["bytes"] == 8 * (338_691 * 576 * 2 + 12 * 20 * (1024 + 64) * 2)
    assert c["flops"] == 8 * 2 * 20 * (1024 + 64) * 338_691
    assert c["flops"] / c["bytes"] == pytest.approx(37.7, rel=0.01)
    assert c["bytes"] / 819e9 > c["flops"] / 197e12
    # a model without latent attention, or a step that says too little
    assert cost.cost(step, {"num_attention_heads": 32, "num_hidden_layers": 32},
                     None) is None
    assert cost.cost({"rows": 12}, PUBLISHED, None) is None
    spec = manifest.load_layer_metric("kernel.mla_decode_roofline")
    assert spec["reader"] == "trace_step_roofline"
    assert spec["params"] == {"ops": "^%mla_decode", "cost": "mla_decode"}


def test_mla_decode_cost_sums_a_burst():
    cost = costs.load("mla_decode")
    one = cost.cost({"rows": 2, "new_tokens": 2, "kv_tokens": 1000}, PUBLISHED, None)
    two = cost.cost({"rows": 2, "new_tokens": 4, "kv_tokens": 1000}, PUBLISHED, None)
    # two tokens a row: contexts of 998 and 1,000 summed over the rows
    assert two["flops"] == one["flops"] * 1998 / 1000


# One decode step's gate-and-up product as the v5e trace names it (my chip
# run, PR 33): 48 pairs padded to 128 rows, 2048 -> 2 x 1536, the seven
# layers' banks of 64 experts seen as one of 448.
GMM_CALL = (
    "%gmm.2 = f32[128,3072]{1,0:T(8,128)S(1)} custom-call(s32[]{:T(128)} "
    "%get-tuple-element.40, s32[449]{0:T(512)S(1)} %pad_add_fusion.3, "
    "s32[449]{0:T(512)S(1)} %dynamic_slice.59, s32[449]{0:T(512)S(1)} "
    "%dynamic_slice.61, s32[1]{0:T(128)} %constant.494, "
    "bf16[128,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.21, "
    "bf16[448,2048,3072]{2,1,0:T(8,128)(2,1)} %bitcast.7), "
    'custom_call_target="tpu_custom_call"')


def test_gated_expert_products_are_costed_by_the_touched_experts():
    """The hybrid's cost module reads these banks as they are: ``[rows, K] x
    [groups, K, N]`` with the window's means of experts touched and pairs
    held; the other layers' groups, which the call does not visit, cost
    nothing."""
    spec = manifest.load_layer_metric("kernel.moe_experts_gated_roofline")
    assert spec["reader"] == "trace_roofline_counted"
    assert spec["params"]["cost"] == "moe_experts_latent"
    c = costs.load(spec["params"]["cost"]).cost(
        {"text": GMM_CALL, "count": 7,
         "counted": {"experts_touched": 32.0, "pairs_held": 48.0}}, {}, None)
    expert = 2048 * 3072 * 2
    assert c["bytes"] == 7 * (32 * expert + 48 * (2048 * 2 + 3072 * 4))
    assert c["flops"] == 7 * 2 * 48 * 2048 * 3072
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
