"""Seconds the planner spent in set-up: ``ChainStats.plan_s`` (analysis,
tile scheduling and the Plan IR, 0 on a plan-cache hit) summed over the
chains run before the window."""


def read(rec):
    return rec["setup_plan_s"]
