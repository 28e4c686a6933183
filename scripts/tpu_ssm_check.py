#!/usr/bin/env python3
"""On the chip: the ``ssm_decode`` kernel, compiled, against the
``jax.numpy`` step at published widths, and how long a call takes.

    chiprun -- python scripts/tpu_ssm_check.py [--moe]

Prints one JSON line per case; the last line is ``{"ok": ...}``. ``--moe``
also times the grouped expert products at the hybrid cell's shapes (how
``lax.ragged_dot`` spends rows that belong to no group).
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from production_stack_tpu.ops import ssm  # noqa: E402


def _time(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def check_decode(B=32, H=128, P=64, N=128, G=8, L=5, slots=33):
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    s = jax.random.normal(ks[0], (L, slots, H, P, N), jnp.float32)
    x = jax.random.normal(ks[1], (B, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H), jnp.float32))
    a = -jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32, 0.0, 2.7))
    bm = jax.random.normal(ks[4], (B, G, N), jnp.float32)
    cm = jax.random.normal(ks[5], (B, G, N), jnp.float32)
    slot_of = jnp.asarray(np.random.RandomState(0).permutation(slots - 1)[:B],
                          jnp.int32)
    li = jnp.int32(3)
    with jax.default_matmul_precision("highest"):  # the step's einsum, exact
        want_y, want_s = ssm.ssm_step(s[li, slot_of], x, dt, a, bm, cm)
    pool = ssm.pack_state(s, G)
    before = np.asarray(pool)
    fn = jax.jit(lambda p: ssm.ssm_decode(
        p, li, slot_of, jnp.exp(dt * a), dt[..., None] * x, bm, cm,
        n_groups=G), donate_argnums=(0,))
    y, pool = fn(pool)
    got_s = ssm.unpack_state(pool[li, slot_of], P)
    err_y = float(jnp.max(jnp.abs(y - want_y)))
    err_s = float(jnp.max(jnp.abs(got_s - want_s)))
    after = np.asarray(pool)
    touched = np.zeros((L, slots), bool)
    touched[3, np.asarray(slot_of)] = True
    others_same = bool(np.array_equal(after[~touched], before[~touched]))

    def loop(p):
        for _ in range(10):
            _, p = ssm.ssm_decode(
                p, li, slot_of, jnp.exp(dt * a), dt[..., None] * x, bm, cm,
                n_groups=G)
        return p

    loop = jax.jit(loop, donate_argnums=(0,))
    pool = loop(pool)
    jax.block_until_ready(pool)
    t0 = time.perf_counter()
    for _ in range(5):
        pool = loop(pool)
    jax.block_until_ready(pool)
    per_call = (time.perf_counter() - t0) / 50
    state_bytes = 2 * B * H * P * N * 4
    out = {"case": "ssm_decode", "err_y": err_y, "err_s": err_s,
           "others_same": others_same, "us_per_call": per_call * 1e6,
           "GBps": state_bytes / per_call / 1e9}
    print(json.dumps(out), flush=True)
    scale = float(jnp.max(jnp.abs(want_y)))
    return err_y <= 1e-3 * max(scale, 1.0) and err_s <= 1e-3 and others_same


def time_moe():
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    E, D, F = 128, 1024, 2688
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    one = (jax.random.normal(k[0], (E, D, F), jnp.float32) / 32).astype(jnp.bfloat16)
    for M, held in ((768, 704), (768, 176), (256, 176), (22528, 5632)):
        xs = jax.random.normal(k[1], (M, D), jnp.float32).astype(jnp.bfloat16)
        sizes = np.full(E, held // E, np.int32)
        sizes[: held - sizes.sum()] += 1
        gs = jnp.asarray(sizes)
        cases = {
            "ragged_dot": jax.jit(lambda x, w, g: jax.lax.ragged_dot(
                x, w, g, preferred_element_type=jnp.float32)),
        }
        for tiling in ((128, 512, 384), (128, 1024, 896), (128, 1024, 2688),
                       (256, 1024, 896)):
            if M % tiling[0] == 0:
                cases[f"megablox gmm {tiling}"] = jax.jit(
                    lambda x, w, g, tiling=tiling: gmm(
                        x, w, g, preferred_element_type=jnp.float32,
                        tiling=tiling))
        for name, fn in cases.items():
            try:
                t = _time(fn, xs, one, gs, n=10)
                print(json.dumps({"case": name, "M": M, "in_groups": held,
                                  "ms": t * 1e3,
                                  "bank_GBps": E * D * F * 2 / t / 1e9}),
                      flush=True)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"case": name, "M": M, "in_groups": held,
                                  "error": str(e)[:300]}), flush=True)


def main(argv) -> int:
    try:
        ok = check_decode()
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"case": "ssm_decode", "error": str(e)[:2000]}),
              flush=True)
        ok = False
    if "--moe" in argv:
        time_moe()
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
