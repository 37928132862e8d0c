"""Counted work of the limb ladders, and the chip's peaks.

Every modular exponentiation that the protocol requires (enc r^n, dec
c^lam, matvec c^k) is counted as e modular squarings at the n^2 width,
with e the bit length of the exponent.  A squaring is priced at 4 L^2
int8 operations, L the bytes of n^2: L^2 byte-limb products for the
schoolbook square and L^2 for its reduction, each a multiply-accumulate
counted as 2 operations.  The count is the work the operation needs, so
it does not change with CRT splitting, window size, Montgomery or
Barrett reduction, or the multiply's implementation.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def n2_bytes(n: int) -> int:
    return -(-(n * n).bit_length() // 8)


def squaring_ops(n: int) -> int:
    """int8 operations of one modular squaring at the n^2 width."""
    L = n2_bytes(n)
    return 4 * L * L


def modexp_ops(n: int, exponents) -> int:
    """int8 operations of the exponentiations mod n^2 by ``exponents``."""
    return squaring_ops(n) * sum(int(e).bit_length() for e in exponents)


def op_ops(op: str, args: tuple, n: int, lam: int) -> int:
    """int8 operations one protocol operation requires: an encryption
    raises r to n, a decryption c to lam, a matvec each ciphertext to its
    matrix entries; ``add`` is a product, not an exponentiation."""
    if op == "enc":
        return modexp_ops(n, [n] * len(args[0]))
    if op == "dec":
        return modexp_ops(n, [lam] * len(args[0]))
    if op == "matvec":
        return modexp_ops(n, [k for row in args[0] for k in row])
    return 0


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    device is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
