"""The window / full attention mix's reference with its window wrong on
purpose (every layer sees the whole context): ``perf/reference/mellum.py``'s
negative control ``window_off`` served up as the reference itself, so that a
whole rehearsal run has something to refuse."""

from perf.reference import mellum as ref

VARIANTS = ("none",)
weights = ref.weights


def teacher_force(cfg, params, sequences, variant):
    return ref.teacher_force(cfg, params, sequences, "window_off")
