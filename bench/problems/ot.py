"""How a solve call of the OT problem is made and answered: the program's
spec, the artifacts the timed call declares, and the answer fields the
reference (``bench/reference/ot.py``) reads beside the cost and the
duals: the plan's nonzero entries."""

WANT = ("cost", "duals", "plan_sparse")


def spec():
    from repro.core.api import OT

    return OT


def answer(view) -> dict:
    sp = view.plan_sparse()
    return {"rows": sp.rows, "cols": sp.cols, "vals": sp.vals}
