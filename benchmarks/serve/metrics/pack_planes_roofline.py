"""The tier's bit-plane pack kernel (``pack_planes_pallas``) as a share of
its roofline.  The kernel reads a u16 slab and writes its 16 bit planes,
two bytes in and two out per element: the least time is those bytes,
read from the shapes in the op's HLO signature, over peak bandwidth.
Summed over the window's calls and divided by their summed device time.

The LZ4 prep kernel (``_prep_kernel``, op ``_device_match``) is not
counted: XLA hands it operands and results in VMEM, so its own op moves
no HBM bytes and its time leaves out the copies that feed it, and no
published peak bounds its integer work.
"""

from servebench import peaks

KERNEL = "pack_planes_pallas"


def read(ctx):
    ks = [k for k in ctx.trace.kernels if k[0] == KERNEL]
    if not ks:
        return None
    least = sum(peaks.least_time_s(0, b, ctx.kind) for _, _, _, b in ks)
    return least / (sum(d for _, _, d, _ in ks) / 1e9) * 100
