"""Algorithmic bytes and operations of one ``hop_adc`` call (u8 codes).

A call scores a (Q, R') frontier: Q queries, R' candidate ids each, M
subspaces of K codewords. Counted, whatever implements the kernel:

* gathered code bytes: Q·R'·M (one byte per sub-code);
* the LUTs, read once per call: Q·M·K·4 (float32);
* the ids in and the distances out: Q·R'·4 each.

The kernel moves whole 512-byte word rows per id; that traffic is not
work and is not credited. Operations: Q·R'·M lookup-adds, far below any
compute peak, so the bound is HBM bandwidth.
"""

# the HLO instruction of the kernel call in the device trace
TRACE_NAMES = ("hop_adc",)


def bytes_per_call(q: int, r: int, m: int, k: int) -> int:
    return q * r * m + q * m * k * 4 + 2 * q * r * 4


def ops_per_call(q: int, r: int, m: int, k: int) -> int:
    return q * r * m
