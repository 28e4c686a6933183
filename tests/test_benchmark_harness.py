"""Benchmark harness ring-2 test: drive the real router + fake engines.

Reference parity: CI runs the perftest/benchmark harness against fake
engines (`router-e2e-test.yml:49-81`).
"""

import asyncio

from aiohttp import web

from benchmarks.multi_round_qa import (
    UserSession,
    WorkloadConfig,
    run_benchmark,
    summarize,
)
from production_stack_tpu.router.app import create_app
from production_stack_tpu.router.parser import parse_args
from production_stack_tpu.testing.fake_engine import create_fake_engine_app

from .router_utils import reset_router_singletons


async def test_multi_round_qa_against_fake_fleet():
    reset_router_singletons()
    runners = []
    try:
        engine_urls = []
        for _ in range(2):
            app = create_fake_engine_app(model="fake/model", speed=5000.0)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            runners.append(runner)
            engine_urls.append(
                f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"
            )
        router_app = create_app(parse_args([
            "--service-discovery", "static",
            "--static-backends", ",".join(engine_urls),
            "--static-models", "fake/model,fake/model",
            "--routing-logic", "roundrobin",
            "--engine-stats-interval", "0.2",
        ]))
        runner = web.AppRunner(router_app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        runners.append(runner)
        router_url = f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"

        cfg = WorkloadConfig(
            num_users=4, num_rounds=2, qps=50.0,
            system_prompt_len=64, chat_history_len=128, answer_len=8,
            model="fake/model", base_url=router_url,
        )
        import time

        t0 = time.time()
        records = await run_benchmark(cfg)
        summary = summarize(records, time.time() - t0)
        assert summary["requests"] == 8
        assert summary["successful"] == 8
        assert summary["ttft_p50_ms"] > 0
        assert summary["generation_tok_per_s"] > 0
        # Sessions really are multi-round: histories grew.
        assert all(r.status == 200 for r in records)
    finally:
        for runner in reversed(runners):
            await runner.cleanup()
        reset_router_singletons()


def test_sharegpt_preprocessing_and_plot(tmp_path):
    """data_preprocessing.py normalizes ShareGPT layouts into the workload
    JSON the harness consumes; plot.py turns per-request CSVs into a sweep
    figure."""
    import csv
    import json
    import sys

    sys.path.insert(0, "benchmarks")
    import data_preprocessing
    import plot as bench_plot

    sharegpt = [
        {"conversations": [
            {"from": "human", "value": "q1 " * 10},
            {"from": "gpt", "value": "a1 " * 10},
            {"from": "human", "value": "q2"},
            {"from": "gpt", "value": "a2"},
        ]},
        {"conversations": [  # single round: filtered by --min-rounds 2
            {"from": "human", "value": "only"},
            {"from": "gpt", "value": "one"},
        ]},
    ]
    src = tmp_path / "sharegpt.json"
    src.write_text(json.dumps(sharegpt))
    out = tmp_path / "workload.json"
    data_preprocessing.main([str(src), "-o", str(out), "--num-users", "4",
                             "--min-rounds", "2"])
    wl = json.loads(out.read_text())
    assert len(wl["users"]) == 1
    assert [r["question"] for r in wl["users"][0]["rounds"]][1] == "q2"

    # plot.py over two synthetic sweep-point CSVs.
    for j, qps in enumerate((1.0, 2.0)):
        with open(tmp_path / f"s{j}.csv", "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["user", "round", "launch_time", "ttft_s",
                        "latency_s", "completion_tokens", "status"])
            for i in range(6):
                w.writerow([i % 3, i // 3, f"{i / qps:.3f}", "0.1200",
                            "1.5000", 64, 200])
    png = tmp_path / "sweep.png"
    bench_plot.main([str(tmp_path / "s0.csv"), str(tmp_path / "s1.csv"),
                     "-o", str(png)])
    assert png.stat().st_size > 1000


async def test_multi_round_qa_sharegpt_workload(tmp_path):
    """--workload mode: rounds replay the real conversation's questions."""
    import json

    from aiohttp import web

    from production_stack_tpu.testing.fake_engine import create_fake_engine_app

    wl = {"users": [{"rounds": [
        {"question": "what is a tpu?", "answer": "a chip"},
        {"question": "and a pod?", "answer": "many chips"},
    ]}]}
    path = tmp_path / "workload.json"
    path.write_text(json.dumps(wl))

    app = create_fake_engine_app(model="fake/model", speed=5000.0)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    url = f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"
    try:
        cfg = WorkloadConfig(
            num_users=2, num_rounds=5, qps=50.0,
            system_prompt_len=32, chat_history_len=64, answer_len=8,
            model="fake/model", base_url=url, workload_path=str(path),
        )
        records = await run_benchmark(cfg)
        # 2 users x min(5, 2 sharegpt rounds) = 4 requests.
        assert len(records) == 4
        assert all(r.status == 200 for r in records)
    finally:
        await runner.cleanup()
