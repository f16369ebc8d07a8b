"""Device time of prompt prefill per 1000 prompt tokens absorbed, ms: the
arithmetic of ``prefill_ms_per_1k_tokens.py`` (the engine's prefill
programs over the tokens of its ``prefill_batch`` and ``prefill_chunk``
spans, in a traced window that opens and closes with the device idle),
read in the hybrid cell."""

from pathlib import Path

from bench.harness import load_module

_SAME = load_module(Path(__file__).with_name("prefill_ms_per_1k_tokens.py"))


def read(r):
    return _SAME.read(r)
