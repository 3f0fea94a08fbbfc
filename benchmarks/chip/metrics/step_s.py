"""Seconds per timestep: the whole measured window over the timesteps
completed in it (host clock; the window ends in a host read)."""


def read(rec):
    return rec["window_s"] / rec["steps"]
