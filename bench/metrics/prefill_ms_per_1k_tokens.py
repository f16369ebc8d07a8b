"""Device time of prompt prefill per 1000 prompt tokens absorbed, ms.

Over the traced window, which opens and closes with the device idle
where the cell's mix sets ``"settle_trace": true`` (the serving loop
then waits on the slot pool), so that every program run in it was
dispatched in it; a cell that lists this metric sets it:
the summed device time of the executions of the engine's jitted prefill
programs (``serve/engine.py``: the pooled chunk and the private chunk,
``jit__lambda``; the singleton chunk, ``jit__slot_prefill``), over the
prompt tokens that the program's ``prefill_batch`` spans (``slots`` x
``chunk``) and ``prefill_chunk`` spans (``chunk``) in the window
absorbed. A run with no such spans or programs reports nothing.
"""

from bench import spans as S
from bench import trace_reduce as TR

PROGRAMS = r"^jit__(lambda|slot_prefill)\("


def tokens(threads: list[list]) -> int:
    n = 0
    for events in threads:
        for e in events:
            args = dict(e.args)
            if e.name == "prefill_batch":
                n += args.get("slots", 0) * args.get("chunk", 0)
            elif e.name == "prefill_chunk":
                n += args.get("chunk", 0)
    return n


def read(r):
    if r.trace is None:
        return None
    n = tokens(S.load(r))
    device_ns = sum(b - a for a, b in TR.complete_modules(r.trace, PROGRAMS))
    if n <= 0 or device_ns <= 0:
        return None
    return device_ns * 1e-6 * 1000 / n
