"""A stretch of the set-up as the harness timed it (``ready_s``,
``history_prefill_s``, ``warmup_s``). params: ``key``."""


def read(params: dict, ctx: dict):
    return ctx["timings"].get(params["key"])
