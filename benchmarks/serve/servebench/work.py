"""The model work an engine call must do, for the readers that count
it from the harness's call spans (``Spans.calls``).

A prefill of ``n`` prompt tokens computes the output head at every row
but keeps only the last row's logits (``ServeEngine.prefill``); the head
of the other ``n - 1`` rows is waste, not work, and is not counted.
Attention counts the positions each token attends to, not the cache
slots the program masks out.
"""

from __future__ import annotations


def head_flops(fam, conf: dict) -> int:
    """FLOPs of the output head for one row."""
    m = fam.dims(conf)
    return 2 * m["V"] * m["d"]


def needed_flops(fam, conf: dict, pos: int, n: int) -> int:
    """FLOPs a call of ``n`` tokens at positions ``pos .. pos+n-1`` must
    do: every token's layers and attention, and the head once."""
    return fam.span_flops(conf, pos, n) - (n - 1) * head_flops(fam, conf)
