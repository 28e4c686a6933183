"""The decoder-hybrid-decoder's reference with its differential attention
wrong on purpose (``lam = 0``: the second softmax is never subtracted):
``perf/reference/phi4flash.py``'s negative control ``lambda_off`` served up
as the reference itself, so that a whole rehearsal run has something to
refuse."""

from perf.reference import phi4flash as ref

VARIANTS = ("none",)
weights = ref.weights


def teacher_force(cfg, params, sequences, variant):
    return ref.teacher_force(cfg, params, sequences, "lambda_off")
