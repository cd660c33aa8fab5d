"""Least HBM bytes of the OT phase loop (core/transport.py), counted from
the code: each propose/accept round (``_grant_round``) reads the (m, n)
int32 rounded costs ``c_int`` once; each phase's push and relabel read
the two (m, n) int32 flow matrices ``f_hi`` and ``f_lo`` and write them
back. The round's one-entry-per-row grant updates are O(m) and are not
counted. The loop is memory-bound (a few integer operations per byte)."""


def loop_bytes(m: int, n: int, rounds: int, phases: int) -> float:
    return 4.0 * m * n * rounds + 16.0 * m * n * phases
