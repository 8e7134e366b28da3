"""Work of the Horner signature layer, counted from the shapes alone.

The count is that of pySigLib's Algorithm 2 (Horner's scheme, arXiv
2509.10613 §2.3), not of an implementation: one path-step of increment z
updates the levels from the top down,

    A_k ← A_k + B_k ⊗ z,   B_k = (…((z/k + A_1) ⊗ z/(k−1) + A_2) ⊗ … ) + A_(k−1)

so level k ≥ 2 costs, per path-step, in f32 operations::

    z/k                                         d
    for i = 1 … k−2:  + A_i, z/(k−i), ⊗         d^i + d + d^(i+1)
    + A_(k−1)                                   d^(k−1)
    ⊗ z, + A_k                                  2·d^k

and level 1 costs d (``A_1 + z``).  A kernel that splits the words by
their leading letters repeats the short chain below the split in every
program; that repetition is not counted.  Bytes are the increments read
and the signature written, never a layout that one kernel happens to use.
"""

from __future__ import annotations

from chipbench.counts import F32, Work


def signature_dim(d: int, depth: int) -> int:
    """Coordinates of levels 1..depth: d + d² + … + d^depth."""
    return sum(d ** k for k in range(1, depth + 1))


def horner_flops_per_step(d: int, depth: int) -> int:
    """f32 operations of one Horner path-step over levels 1..depth."""
    total = d
    for k in range(2, depth + 1):
        total += d + d ** (k - 1) + 2 * d ** k
        total += sum(d ** i + d + d ** (i + 1) for i in range(1, k - 1))
    return total


def horner(paths: int, steps: int, d: int, depth: int) -> Work:
    """Signatures of ``paths`` paths of ``steps`` increments each."""
    return Work(flops=float(paths * steps * horner_flops_per_step(d, depth)),
                bytes=float(paths * (steps * d + signature_dim(d, depth))
                            * F32))
