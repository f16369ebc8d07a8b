"""Recurrent state that a decode step's program reads, MiB.

The median, over the program's ``decode_batch`` spans (``serve/engine.py``)
wholly inside the traced window, of the sum of their ``ssm_state_bytes``
(Mamba2's state and conv tails) and ``taylor_state_bytes`` (the Taylor
states of the attention layers) arguments. Today's decode step reads the
whole slot pool; the number falls when a step reads only live slots or
keeps smaller state. A program whose spans carry no such arguments
reports nothing.
"""

import statistics

from bench import spans as S

ARGS = ("ssm_state_bytes", "taylor_state_bytes")


def state_mib(threads: list[list]) -> float | None:
    per_step = []
    for events in threads:
        for e in events:
            args = dict(e.args)
            if e.name == "decode_batch" and all(a in args for a in ARGS):
                per_step.append(sum(args[a] for a in ARGS) / 2**20)
    return statistics.median(per_step) if per_step else None


def read(r):
    return state_mib(S.load(r))
