"""On-device token sampling: temperature / top-k / top-p / min-p + penalties.

Runs inside the engine's jitted step so logits never leave the device (only
the sampled token ids — ``[B]`` int32 — cross to host). Truncated to the top
``SAMPLE_K_CAP`` logits before filtering: exact for any vocab when the cap
covers it, and the standard serving approximation for 100k+ vocabs (mass
outside the top-256 is negligible post-temperature).

Greedy rows (temperature ≈ 0) take a pure argmax of the raw logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SAMPLE_K_CAP = 256
# Top-logprob entries returned per sampled token (OpenAI allows up to 20).
LOGPROBS_K = 20
# Packed row layout (see sample_tokens_packed): token, chosen logprob,
# LOGPROBS_K top logprobs, LOGPROBS_K top token ids.
PACKED_WIDTH = 2 + 2 * LOGPROBS_K
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def sample_tokens(
    logits: jax.Array,  # [B, V] float32
    temps: jax.Array,  # [B]
    top_ps: jax.Array,  # [B]
    top_ks: jax.Array,  # [B] int32 (<=0: disabled)
    min_ps: jax.Array,  # [B]
    seeds: jax.Array,  # [B] uint32 (per-seq, per-step)
    greedy_only: bool = False,
) -> jax.Array:
    """``greedy_only`` is a trace-time constant set by the runner when every
    row in the batch is greedy: skips the top-k/softmax/gumbel machinery
    entirely (a top_k over a 128k vocab costs real milliseconds per decode
    scan step, and greedy batches — the common serving case — need only the
    argmax XLA fuses into the unembed matmul's epilogue)."""
    if greedy_only:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    B, V = logits.shape
    K = min(V, SAMPLE_K_CAP)
    greedy = temps <= 1e-5
    t = jnp.maximum(temps, 1e-5)[:, None]

    vals, idxs = jax.lax.top_k(logits, K)  # [B, K] descending
    scaled = vals / t
    probs = jax.nn.softmax(scaled, axis=-1)

    col = jnp.arange(K, dtype=jnp.int32)[None, :]
    kk = jnp.where(top_ks <= 0, K, jnp.minimum(top_ks, K))[:, None]
    keep = col < kk
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < top_ps[:, None]  # keep first token crossing top_p
    keep &= probs >= min_ps[:, None] * probs[:, :1]
    keep = keep.at[:, 0].set(True)

    def one(seed, row, mask):
        g = jax.random.gumbel(jax.random.PRNGKey(seed), (K,), jnp.float32)
        return jnp.argmax(jnp.where(mask, row + g, _NEG))

    choice = jax.vmap(one)(seeds, scaled, keep)  # [B]
    sampled = jnp.take_along_axis(idxs, choice[:, None], axis=1)[:, 0]
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled).astype(jnp.int32)


def sample_tokens_packed(
    logits: jax.Array,  # [B, V] float32
    temps: jax.Array,
    top_ps: jax.Array,
    top_ks: jax.Array,
    min_ps: jax.Array,
    seeds: jax.Array,
    with_logprobs: bool = False,
    greedy_only: bool = False,
) -> jax.Array:
    """Sample into ONE packed f32 array — ``[token]`` per row, or with
    ``with_logprobs`` (a trace-time constant: the runner compiles separate
    no-logprobs/logprobs step variants, like its penalties gating)
    ``[token, chosen_logprob, top_lps(K), top_ids(K)]``.

    One packed array = one host fetch per step. Token ids ride as f32 — exact for any vocab < 2^24. Logprobs are raw
    ``log_softmax(logits)`` (pre-temperature, the OpenAI/vLLM convention);
    gating them keeps the full-vocab log_softmax + top-k out of the
    latency-critical decode path when nobody asked."""
    tokens = sample_tokens(
        logits, temps, top_ps, top_ks, min_ps, seeds, greedy_only=greedy_only
    )
    if not with_logprobs:
        return tokens[:, None].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)  # [B, V]
    chosen = jnp.take_along_axis(logp, tokens[:, None], axis=1)  # [B, 1]
    top_lps, top_ids = jax.lax.top_k(logp, LOGPROBS_K)
    return jnp.concatenate(
        [
            tokens[:, None].astype(jnp.float32),
            chosen,
            top_lps,
            top_ids.astype(jnp.float32),
        ],
        axis=1,
    )


def unpack_sampled(packed) -> tuple:
    """Host-side view of a packed row array (any leading dims):
    (tokens int, chosen_lp, top_lps [..., K], top_ids [..., K] int)."""
    import numpy as np

    tokens = packed[..., 0].astype(np.int64)
    chosen = packed[..., 1]
    top_lps = packed[..., 2 : 2 + LOGPROBS_K]
    top_ids = packed[..., 2 + LOGPROBS_K :].astype(np.int64)
    return tokens, chosen, top_lps, top_ids


def apply_logit_bias(
    logits: jax.Array,  # [B, V] float32
    bias_ids: jax.Array,  # [B, Nb] int32, pad = V (dropped)
    bias_vals: jax.Array,  # [B, Nb] float32
) -> jax.Array:
    """OpenAI ``logit_bias``: additive per-token offsets before sampling."""
    B = logits.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    return logits.at[rows, bias_ids].add(bias_vals, mode="drop")


def apply_allowed_mask(
    logits: jax.Array,  # [B, V] float32
    allowed_ids: jax.Array,  # [B, Na] int32, pad = V (dropped)
    allow_free: jax.Array,  # [B] bool — True: row is unconstrained
) -> jax.Array:
    """Guided decoding: restrict each constrained row to its allowed token
    set (everything else to -inf); unconstrained rows pass through."""
    B, V = logits.shape
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    mask = (
        jnp.zeros((B, V), jnp.bool_)
        .at[rows, allowed_ids]
        .set(True, mode="drop")
    )
    mask = mask | allow_free[:, None]
    return jnp.where(mask, logits, _NEG)


def apply_penalties_counts(
    logits: jax.Array,  # [B, V] float32
    prompt_seen: jax.Array,  # [B, V] bool
    out_counts: jax.Array,  # [B, V] float32 (output-token occurrence counts)
    presence: jax.Array,  # [B]
    frequency: jax.Array,  # [B]
    repetition: jax.Array,  # [B]
) -> jax.Array:
    """Penalty math over *dense* per-vocab state. This is the form a
    decode-burst scan can carry: ``out_counts`` updates on-device after
    every sampled token (``multi_step``'s scan carry in engine/runner.py),
    so penalty/repetition rows ride multi-step bursts instead of forcing
    the whole batch to n=1 single-step dispatches."""
    seen = prompt_seen | (out_counts > 0)
    rep = repetition[:, None]
    logits = jnp.where(
        seen, jnp.where(logits > 0, logits / rep, logits * rep), logits
    )
    logits = logits - frequency[:, None] * out_counts
    logits = logits - presence[:, None] * (out_counts > 0).astype(jnp.float32)
    return logits


def apply_penalties(
    logits: jax.Array,  # [B, V] float32
    prompt_tokens: jax.Array,  # [B, Pp] int32, pad = V (dropped)
    output_tokens: jax.Array,  # [B, Po] int32, pad = V (dropped)
    presence: jax.Array,  # [B]
    frequency: jax.Array,  # [B]
    repetition: jax.Array,  # [B]
) -> jax.Array:
    """vLLM-convention penalties: repetition over prompt+output occurrence;
    presence/frequency over output counts. Token-id-array form used by the
    single-step path; scatters into the dense state and delegates to
    :func:`apply_penalties_counts` so the two paths cannot drift."""
    B, V = logits.shape
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    out_counts = (
        jnp.zeros((B, V), jnp.float32)
        .at[rows, output_tokens]
        .add(1.0, mode="drop")
    )
    prompt_seen = (
        jnp.zeros((B, V), jnp.bool_)
        .at[rows, prompt_tokens]
        .set(True, mode="drop")
    )
    return apply_penalties_counts(
        logits, prompt_seen, out_counts, presence, frequency, repetition
    )
