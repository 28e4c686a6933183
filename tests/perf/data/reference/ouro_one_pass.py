"""The looped stack's reference with its loop wrong on purpose (the stack
run once): ``perf/reference/ouro.py``'s negative control ``one_pass`` served
up as the reference itself, so that a whole rehearsal run has something to
refuse."""

from perf.reference import ouro as ref

VARIANTS = ("none",)
weights = ref.weights


def teacher_force(cfg, params, sequences, variant):
    return ref.teacher_force(cfg, params, sequences, "one_pass")
