"""A reference module that is wrong on purpose: the Mistral reference's
log-probabilities with the vocabulary reversed. A configuration that names
it must come out not ``correct``, which shows that the named module, and not
the default one, decided."""

from perf.reference import mistral

VARIANTS = ("none",)
weights = mistral.weights


def teacher_force(cfg, params, sequences, variant):
    return [(lps[:, ::-1], gap) for lps, gap in
            mistral.teacher_force(cfg, params, sequences, variant)]
