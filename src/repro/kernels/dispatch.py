"""The one backend dispatch point for the tier's device kernels.

``pack_planes_slab`` (bit-plane pack), ``kv_pack`` (the tier's KV
transform with the pack, ``kv_pack_planes_slab``), ``match_events_slab``
(LZ4 match scan) and ``page_scores`` (PNM page scoring) each have a
device path — a pallas kernel under jit — and a numpy twin.
:func:`choose` picks one per call:

* on a TPU backend the device path runs, compiled for the chip;
* on any other backend the numpy twin runs;
* ``force="numpy"`` pins the twin anywhere; ``force="device"`` (or its
  spelling ``"pallas"``) pins the device path, in pallas interpret mode
  off the TPU — the parity tests use that.

Backend errors propagate: a broken runtime is never read as "cpu".
Every decision adds one to the ``repro.obs`` counter
``kernel.<kernel>.<path>``, so a caller can prove which path a run took.
"""

from __future__ import annotations

import jax

from .. import obs

DEVICE = "device"          # compiled for the TPU
INTERPRET = "interpret"    # the same kernel, pallas interpret mode
NUMPY = "numpy"            # the numpy twin


def choose(kernel: str, force: str | None = None) -> str:
    """The path for one call of ``kernel``: DEVICE, INTERPRET or NUMPY."""
    if force not in (None, NUMPY, DEVICE, "pallas"):
        raise ValueError(f"unknown force {force!r} for {kernel}")
    tpu = jax.default_backend() == "tpu"
    if force == NUMPY or (force is None and not tpu):
        path = NUMPY
    else:
        path = DEVICE if tpu else INTERPRET
    obs.count(f"kernel.{kernel}.{path}")
    return path


def bucket(n: int) -> int:
    """The power of two ≥ ``n`` that device paths pad a dimension to, so
    a serve run compiles a handful of shapes rather than one per call."""
    return 1 << max(n - 1, 0).bit_length()
