"""Locate the decode-step bottleneck at the bench shape (8 users x 21k ctx).

Times, each as a jit that loops the op N times over a fori_loop (so the
per-call dispatch cost amortizes away):
  1. attention kernel alone, one layer
  2. attention across all 16 layers (scan, no MLP)
  3. KV scatter alone across 16 layers
  4. the full model decode step (the runner's decode program's shape)

The host's side of a decode step is read from the engine itself: the
``pst.*`` phases (``pst_engine_step_phase_seconds``, and the spans of a
``POST /debug/profile`` capture; docs/observability.md "Profiling").
"""

import time
import functools

import numpy as np
import jax
import jax.numpy as jnp

from production_stack_tpu.ops.paged_attention_pallas import pallas_paged_attention

L, nb, bs, KH, hd, H = 16, 1408, 128, 8, 128, 16
B, W, live = 8, 256, 21000
lanes = KH * hd
scale = 1.0 / np.sqrt(hd)


def timed(fn, *args, iters=10, inner=8):
    """fn must take (*args) and return something; we scan it inner times."""
    jfn = jax.jit(fn)
    out = jfn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args)
    jax.block_until_ready(out)
    per_call = (time.perf_counter() - t0) / iters
    return per_call / inner


def main():
    import sys
    model_only = "--model-only" in sys.argv
    rng = np.random.default_rng(0)
    if model_only:
        model_leg(rng)
        return
    kv = jnp.zeros((L, nb, 2, bs, lanes), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.bfloat16)
    # 8 x 256 > nb: page ids may repeat across rows (timing only).
    tables = jnp.asarray(
        rng.integers(0, nb, size=(B, W)).astype(np.int32)
    )
    lens = jnp.full((B,), live, jnp.int32)
    pos = jnp.full((B, 1), live - 1, jnp.int32)
    INNER = 8

    def attn_one_layer(q, kv):
        def body(i, acc):
            o = pallas_paged_attention(q, kv, tables, lens, pos, 0, scale=scale)
            return acc + o.astype(jnp.float32)
        return jax.lax.fori_loop(0, INNER, body, jnp.zeros(q.shape, jnp.float32))

    t = timed(attn_one_layer, q, kv, inner=INNER)
    gbs = B * live * 2 * KH * hd * 2 / t / 1e9
    print(f"attn 1 layer : {t*1e3:7.3f} ms  ({gbs:5.0f} GB/s live-KV)")

    def attn_16(q, kv):
        def body(i, acc):
            o = pallas_paged_attention(q, kv, tables, lens, pos, i % L, scale=scale)
            return acc + o.astype(jnp.float32)
        return jax.lax.fori_loop(0, INNER * L, body, jnp.zeros(q.shape, jnp.float32))

    t16 = timed(attn_16, q, kv, inner=INNER)  # per 16-layer sweep
    print(f"attn 16 layer: {t16*1e3:7.3f} ms  ({B*live*2*KH*hd*2*L/t16/1e9:5.0f} GB/s)")

    flat_write = jnp.asarray(
        (np.arange(B) * bs + live % bs).astype(np.int32)
    )
    kvd = jnp.asarray(rng.standard_normal((2 * B, lanes)), jnp.bfloat16)

    def scatter_16(kv):
        def body(i, kv):
            idx = jnp.concatenate([
                (i % L) * nb * 2 * bs + flat_write,
                (i % L) * nb * 2 * bs + flat_write + bs,
            ])
            flat = kv.reshape(L * nb * 2 * bs, lanes)
            flat = flat.at[idx].set(kvd, mode="drop")
            return flat.reshape(L, nb, 2, bs, lanes)
        return jax.lax.fori_loop(0, INNER * L, body, kv)

    jscatter = jax.jit(scatter_16, donate_argnums=(0,))
    kv2 = jscatter(kv)
    jax.block_until_ready(kv2)
    t0 = time.perf_counter()
    for _ in range(6):
        kv2 = jscatter(kv2)
    jax.block_until_ready(kv2)
    ts = (time.perf_counter() - t0) / 6 / INNER
    print(f"scatter x16  : {ts*1e3:7.3f} ms per 16-layer sweep")


def model_leg(rng):
    # Full engine decode step (one token for 8 seqs).
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.engine.sequence import Sequence, SamplingParams

    cfg = EngineConfig(
        model="llama-1b", max_model_len=32768, block_size=bs,
        num_kv_blocks=nb, max_num_seqs=16, max_prefill_tokens=1024,
        attn_impl="pallas", num_decode_steps=2, min_decode_bucket=8,
    )
    runner = ModelRunner(cfg)
    seqs = []
    blocks_per = -(-live // bs)  # 165 pages of 128 tokens for 21k ctx
    assert B * blocks_per <= nb, "synthetic tables must stay in range"
    for i in range(B):
        s = Sequence(f"s{i}", list(range(100)), SamplingParams(max_tokens=8))
        s.block_ids = list(range(i * blocks_per, (i + 1) * blocks_per))
        s.output_token_ids = [1] * (live - 100)
        s.num_computed_tokens = live
        seqs.append(s)
    runner.execute_decode(seqs)  # compile
    t0 = time.perf_counter()
    for _ in range(10):
        out = runner.execute_decode(seqs)
    dt = (time.perf_counter() - t0) / 10
    print(f"model decode : {dt*1e3:7.3f} ms per step (incl dispatch)")
    t0 = time.perf_counter()
    for _ in range(5):
        out = runner.execute_decode_multi(seqs, 2)
    dt = (time.perf_counter() - t0) / 5
    print(f"decode burst2: {dt*1e3:7.3f} ms per 2-token burst")


if __name__ == "__main__":
    main()
