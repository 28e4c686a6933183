"""Seconds of one start-up phase as the engine reports them
(``pst_engine_startup_seconds{phase=...}``). params: ``phase``."""


def read(params: dict, ctx: dict):
    for lab, v in ctx["prom_after"].get("pst_engine_startup_seconds", []):
        if lab.get("phase") == params["phase"]:
            return v
    return None
