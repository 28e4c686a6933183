"""The self-drafting class's reference with one equation wrong on purpose
(the full-attention layers rotated like the window layers):
``perf/reference/exaone_moe.py``'s negative control ``rope_on_full`` served
up as the reference itself, so that a whole rehearsal run has something to
refuse."""

from perf.reference import exaone_moe as ref

VARIANTS = ("none",)
weights = ref.weights


def teacher_force(cfg, params, sequences, variant):
    return ref.teacher_force(cfg, params, sequences, "rope_on_full")
