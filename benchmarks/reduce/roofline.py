"""The verify kernels' share of the chip's roofline.

Least time for the signatures the window verified, over the device time of
the verify programs in the trace.  Operations are counted per signature
VERIFIED, whatever implements it (a batch verdict that needs fewer group
operations reads higher, as it should):

- one double-scalar multiplication [s]B - [h]A by the textbook joint
  double-and-add: 256 doublings and 128 additions (half the bits of two
  256-bit scalars set), 8 field multiplications each in extended coordinates
  -> (256 + 128) * 8 = 3,072 field multiplications;
- two point decompressions (A and R) and one inversion for the final
  compression or comparison, ~265 field multiplications each (an exponentiation
  by a 255-bit constant: 254 squarings and 11 multiplications) -> 795;
- a 255-bit field multiplication as 32 x 32 byte limbs = 1,024 int8
  multiply-accumulates = 2,048 operations (the reduction is folded in: the
  roofline counts the least work, not this implementation's);
- SHA-512 of R || A || M is a few thousand 64-bit operations and is left out:
  under 1% of the above.

So 3,867 * 2,048 = 7,919,616 int8 operations a signature: 20.2 ns at the v5e's
393 TOP/s.  Bytes: the lane's inputs (32 B key index or key, 64 B signature,
the signed message) and one verdict byte: ~212 B, 0.26 ns at 819 GB/s.  The
compute bound binds by two orders of magnitude.
"""

from __future__ import annotations

import json
import os
import re

FIELD_MULS_PER_SIG = (256 + 128) * 8 + 3 * 265
OPS_PER_FIELD_MUL = 32 * 32 * 2
PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


def ops_and_bytes(signatures: int, message_bytes: int) -> tuple:
    ops = signatures * FIELD_MULS_PER_SIG * OPS_PER_FIELD_MUL
    return ops, signatures * (32 + 64 + message_bytes + 1)


def least_seconds(signatures: int, message_bytes: int, device_kind: str) -> tuple:
    """(seconds, which bound binds); an unknown device kind is an error."""
    with open(PEAKS) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    p = peaks[device_kind]
    ops, nbytes = ops_and_bytes(signatures, message_bytes)
    by_ops, by_bytes = ops / p["int8_ops_per_s"], nbytes / p["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), "compute" if by_ops >= by_bytes else "memory"


def reduce(ctx, modules: str, **args):
    trace = ctx["trace"]
    pat = re.compile(modules)
    dev = sum(d for _, n, _, d in trace.modules if pat.search(n))
    sigs = len(trace.spans_named("bench:entry")) * ctx["lanes_per_call"]
    if dev <= 0 or not sigs:
        return None
    least, _ = least_seconds(sigs, ctx["message_bytes"], ctx["device_kind"])
    return 100.0 * least / dev
