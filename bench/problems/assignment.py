"""How a solve call of the assignment problem is made and answered: the
program's spec, the artifacts the timed call declares, and the answer
fields the reference (``bench/reference/assignment.py``) reads beside the
cost and the duals."""

WANT = ("cost", "duals", "matching")


def spec():
    from repro.core.api import ASSIGNMENT

    return ASSIGNMENT


def answer(view) -> dict:
    return {"matching": view.matching()}
