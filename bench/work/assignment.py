"""Least HBM bytes of the assignment phase loop (core/pushrelabel.py),
counted from the code of one round: each propose/accept round of the
greedy maximal matching reads the (m, n) int32 rounded costs ``c_int``
once. The per-phase push and relabel touch O(m + n) words and are not
counted. The loop is memory-bound (a few integer operations per byte)."""


def loop_bytes(m: int, n: int, rounds: int, phases: int) -> float:
    return 4.0 * m * n * rounds
