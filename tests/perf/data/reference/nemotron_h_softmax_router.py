"""The hybrid's reference with its router wrong on purpose (scores by
softmax over the experts instead of the sigmoid): ``perf/reference/
nemotron_h.py``'s negative control ``softmax_router`` served up as the
reference itself, so that a whole rehearsal run has something to refuse."""

from perf.reference import nemotron_h as ref

VARIANTS = ("none",)
weights = ref.weights


def teacher_force(cfg, params, sequences, variant):
    return ref.teacher_force(cfg, params, sequences, "softmax_router")
