"""Seconds from the process's start to the first timed step: imports,
building the fields from the seed, planning, compiling or loading programs,
and the warm-up steps (host clock)."""


def read(rec):
    return rec["setup_s"]
