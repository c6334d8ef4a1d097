"""Size bucketing.

TPU-native replacement for the reference mempool + data-area resize
machinery (`src/data/dbcsr_data_types.F:62-81`, resize factor 1.2):
device array extents are rounded up to a coarse bucket so repeated
multiplies with slightly different sparsity hit the XLA jit cache
instead of recompiling.
"""

from __future__ import annotations


def bucket_size(n: int, minimum: int = 16) -> int:
    """Round ``n`` up to {1,2,4,...}×2^k with ~25% max slack."""
    if n <= 0:
        return 0
    if n <= minimum:
        return minimum
    # next value of form {4,5,6,7} * 2^k  (<=25% over-allocation)
    k = max((n - 1).bit_length() - 3, 0)
    step = 1 << k
    return ((n + step - 1) // step) * step


def bucket_pow2(n: int, minimum: int = 16) -> int:
    """Round ``n`` up to a power of two (at least ``minimum``): up to 2x
    slack, for arrays whose counts move by more than `bucket_size`'s
    25% steps from one call to the next and whose programs should
    still be shared."""
    if n <= 0:
        return 0
    return max(minimum, 1 << (n - 1).bit_length())


def bucket_pow4(n: int, minimum: int = 1) -> int:
    """Round ``n`` up to a power of four (at least ``minimum``): for an
    extent whose slack costs memory only (a loop's trip count that the
    loop reads at run time), where counts move by up to 2x and the
    programs they key should still be shared."""
    if n <= 0:
        return 0
    return max(minimum, 1 << (2 * (((n - 1).bit_length() + 1) // 2)))


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)
