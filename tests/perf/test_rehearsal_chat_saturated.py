"""A cell is data files and one entry. The mix of a saturated chat cell
(unrelated single prompts, a closed-loop client for every row, no shared
prefix, probes behind the empty prefix) rehearsed through a whole run on the
CPU at the tiny configuration (``test_rehearsal.py`` has the harness's other
paths; this run has a file of its own so that the two share no worker's
time)."""

from perf import manifest

from .test_rehearsal import DIRS, _run, bench  # noqa: F401 (bench: a fixture)


def test_chat_saturated_files_rehearsed_at_the_tiny_size(bench, tmp_path):
    """``data/traffic/tiny-chat-saturated.json`` (probes that need no
    ``contexts``, two fresh checks) through a whole traced run: every
    generic per-layer metric that needs no device trace is read (with no
    chip there is no profile, and the trace readers leave theirs out), and
    nothing was cached."""
    mix = manifest.load_mix("tiny-chat-saturated", DIRS["traffic"])
    assert mix["generator"] == "closed_loop" and mix["shared_prefix_tokens"] == 0
    assert "contexts" not in mix["warmup"]["probes"]
    cell = "tiny-dense-int4.tiny-chat-saturated"
    line = _run(bench, cell, True, tmp_path)
    got = set(line["metrics"])
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert got <= owed and not any(n.startswith("kernel.") for n in got)
    assert {"client.ttft_p50_ms", "client.itl_p95_ms", "engine.queue_wait_p95_ms",
            "sched.cached_prompt_share", "runner.prefill_step_mean_ms",
            "runner.decode_step_mean_ms", "runner.compiles_in_window",
            "startup.ready_s", "test.requests_served"} <= got
    # random prompts share nothing but a chance page
    assert line["metrics"]["sched.cached_prompt_share"]["value"] < 5
    assert line["attempted"] >= 8  # every client completed a request
