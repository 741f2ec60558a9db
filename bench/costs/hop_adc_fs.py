"""Algorithmic bytes and operations of one ``hop_adc_fs`` call (fs4 codes).

A call scores a (Q, R') frontier against 4-bit codes of M subspaces,
two sub-codes per byte, with uint8 LUTs of 16 entries per subspace:

* gathered code bytes: Q·R'·ceil(M/2);
* the uint8 LUTs, read once per call: Q·M·16;
* the ids in and the accumulators out: Q·R'·4 each.

Operations: Q·R'·M integer lookup-adds; the bound is HBM bandwidth.
"""

# the HLO instruction of the kernel call in the device trace
TRACE_NAMES = ("hop_adc_fs",)


def bytes_per_call(q: int, r: int, m: int, k: int) -> int:
    return q * r * ((m + 1) // 2) + q * m * 16 + 2 * q * r * 4


def ops_per_call(q: int, r: int, m: int, k: int) -> int:
    return q * r * m
