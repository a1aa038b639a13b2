"""Plain reference for a gradient all-reduce, independent of the program.

Each of n chips contributes a float32 array g_i of one leaf's shape; a
correct all-reduce leaves on every chip the sum s = sum_i g_i. Summed in
float32, in any order, n values take n - 1 roundings, and every element
of the answer lies within

    gamma_{n-1} * sum_i |g_i|,   gamma_k = k u / (1 - k u),  u = 2**-24

of the exact sum (Higham, Accuracy and Stability of Numerical Algorithms,
2nd ed., Sec. 4.2); to first order that is 3 * 2**-24 * sum_i |g_i| for
four chips. ``sum_and_bound`` computes the sum and that bound in
float64, and ``excess`` counts the elements of an answer outside it (a
NaN counts). ``sum_bf16`` is the same sum computed in bfloat16, the
precision below the configuration's float32: every contribution and
every partial sum rounded to it, the control that the comparison has to
reject.
"""
from __future__ import annotations

import numpy as np

U = 2.0 ** -24


def bound_factor(n: int) -> float:
    """gamma_{n-1}: the relative bound of a float32 sum of n values."""
    k = n - 1
    return k * U / (1.0 - k * U)


def sum_and_bound(contribs) -> tuple[np.ndarray, np.ndarray]:
    """n float32 contributions of m elements each (an (n, m) array or a
    list) -> the sum and the bound, each (m,) float64. Each add
    widens its float32 operand exactly; no float64 copy of a
    contribution is made."""
    s = contribs[0].astype(np.float64)
    a = np.abs(s)
    for g in contribs[1:]:
        np.add(s, g, out=s)
        np.add(a, np.abs(g), out=a)
    a *= bound_factor(len(contribs))
    return s, a


def excess(answer: np.ndarray, s: np.ndarray, bound: np.ndarray) -> int:
    """Elements of ``answer`` farther than ``bound`` from ``s``."""
    gap = np.subtract(answer, s, dtype=np.float64)
    np.abs(gap, out=gap)
    return int(np.count_nonzero(~(gap <= bound)))


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> the nearest bfloat16 (ties to even), kept as float32;
    for finite values."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def sum_bf16(contribs: np.ndarray) -> np.ndarray:
    """The sum of n float32 contributions computed in bfloat16."""
    acc = round_bf16(contribs[0])
    for g in contribs[1:]:
        acc = round_bf16(acc + round_bf16(g))
    return acc
