"""Host time of a decode step that the device waits for, ms.

Over the program's ``engine_step`` spans (``serve/engine.py``) wholly
inside the traced window that hold a ``decode_batch`` span and no
``prefill_*`` one: the median of the step's duration less that of its
``decode_readback`` span, in which the host waits on the device. What is
left (admission, planning, building and dispatching the step's inputs,
emitting tokens, bookkeeping) is host work with the device idle. A
program without these spans reports nothing.
"""

import statistics

from bench import spans as S


def host_ms(threads: list[list]) -> float | None:
    per_step = []
    for events in threads:
        for step in (e for e in events if e.name == "engine_step"):
            inside = S.children(step, events)
            names = {e.name for e in inside}
            if "decode_batch" not in names or any(
                    n.startswith("prefill_") for n in names):
                continue
            wait = sum(e.end - e.start for e in inside
                       if e.name == "decode_readback")
            per_step.append((step.end - step.start - wait) * 1e-6)
    return statistics.median(per_step) if per_step else None


def read(r):
    return host_ms(S.load(r))
