"""Host seconds of ``optimize_network`` for the plan, inside set-up."""


def read(rec):
    return rec["work"].get("plan_solve_s")
