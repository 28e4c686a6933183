"""The gated-delta-rule hybrid's reference with its recurrence wrong on
purpose (``g = 0``: the state never decays): ``perf/reference/qwen3_next.py``'s
negative control ``decay_off`` served up as the reference itself, so that a
whole rehearsal run has something to refuse."""

from perf.reference import qwen3_next as ref

VARIANTS = ("none",)
weights = ref.weights


def teacher_force(cfg, params, sequences, variant):
    return ref.teacher_force(cfg, params, sequences, "decay_off")
