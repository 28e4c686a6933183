"""Worker script for the multi-process multi-host engine tests.

Each process gets 4 virtual CPU devices (8 global) and joins
jax.distributed. Process 0 runs real generation through the scheduler and
prints token ids; process 1 runs the follower loop. The parent test asserts
process 0's output matches the single-host oracle.

Usage: python multihost_worker.py <coordinator_port> <process_id> [mode]

Modes:
  pp_tp    (default) pp=2 x tp=4 — layer stages span the two hosts
  dp_pp_tp dp=2 x pp=2 x tp=2 — adds in-engine data-parallel rows
  join     pp=2 x tp=4; the second prompt arrives while the first decodes in
           a chain, and joins it behind its own prefill: the follower runs
           the prefill, the splice and the chained step in the same order.
  dirty    pp=2 x tp=4, but process 0 EXITS WITHOUT announcing shutdown
           after generating (crash simulation); the follower must notice
           the lost primary and exit rather than wedge in a dead collective.
"""

import os
import sys
import time

port, pid = sys.argv[1], int(sys.argv[2])
mode = sys.argv[3] if len(sys.argv) > 3 else "pp_tp"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "").replace("--xla_force_host_platform_device_count=8", "")
    + " --xla_force_host_platform_device_count=4"
).strip()
os.environ["PST_FORCE_PALLAS_INTERPRET"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from production_stack_tpu.parallel.distributed import (  # noqa: E402
    DistributedConfig,
    maybe_init_distributed,
)

maybe_init_distributed(
    DistributedConfig(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())

from production_stack_tpu.engine.config import EngineConfig  # noqa: E402

if mode == "dp_pp_tp":
    parallel = dict(
        data_parallel_size=2, pipeline_parallel_size=2, tensor_parallel_size=2
    )
else:
    parallel = dict(pipeline_parallel_size=2, tensor_parallel_size=4)

cfg = EngineConfig(
    model="tiny-llama-debug",
    max_model_len=128,
    block_size=8,
    num_kv_blocks=64,
    max_num_seqs=4,
    max_prefill_tokens=32,
    attn_impl="gather",
    # join: a chain of one member has a second row for the arrival
    min_decode_bucket=2 if mode == "join" else 1,
    **parallel,
)

PROMPT = [3, 17, 98, 255, 42, 7, 11, 200, 150, 31, 8, 77, 123]
PROMPT2 = [5, 9, 301, 44, 260, 18, 2, 90, 33]

if pid == 0:
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.multihost import StepPublisher
    from production_stack_tpu.engine.sequence import SamplingParams

    engine = LLMEngine(cfg)
    engine.runner.publisher = StepPublisher()
    prompts = [list(PROMPT)] + ([list(PROMPT2)] if mode == "dp_pp_tp" else [])
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    if mode == "join":
        outs = [{"token_ids": []}, {"token_ids": []}]
        engine.add_request("0", prompt_token_ids=list(PROMPT), sampling=sp)
        steps = 0
        while engine.has_work():
            for out in engine.step():
                outs[int(out.request_id)]["token_ids"] += out.new_token_ids
            steps += 1
            if steps == 3:
                assert engine.runner.burst_in_flight
                engine.add_request(
                    "1", prompt_token_ids=list(PROMPT2), sampling=sp)
        print(f"KEPT:{engine.chain_kept_prefills_total}")
    else:
        outs = engine.generate(prompts, sp)
    for i, out in enumerate(outs):
        suffix = str(i) if i else ""
        print(f"TOKENS{suffix}:" + ",".join(str(t) for t in out["token_ids"]))
    sys.stdout.flush()
    if mode == "dirty":
        os._exit(0)  # crash simulation: no publisher.shutdown()
    engine.runner.publisher.shutdown()
else:
    from production_stack_tpu.engine.multihost import (
        make_follower_runner,
        run_follower,
    )

    t0 = time.time()
    run_follower(make_follower_runner(cfg))
    print(f"FOLLOWER-DONE after {time.time()-t0:.1f}s")
