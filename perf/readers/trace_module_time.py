"""Mean device time, in milliseconds, of the executed programs (``XLA
Modules`` events) whose name matches. params: ``pattern`` (regular
expression over the program's name without its id, such as
``^jit_pst_decode_step``). None where no program of that name ran."""

import re

from perf import host_trace


def read(params: dict, ctx: dict):
    t = host_trace.of_run(ctx)
    if not t:
        return None
    pat = re.compile(params["pattern"])
    count = sum(n for name, (n, _) in t["modules"].items() if pat.search(name))
    if not count:
        return None
    seconds = sum(s for name, (_, s) in t["modules"].items() if pat.search(name))
    return seconds / count * 1e3
