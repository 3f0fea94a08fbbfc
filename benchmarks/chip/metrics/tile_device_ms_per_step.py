"""Device milliseconds per timestep of the jitted tile programs
(``jit_tile_fn`` modules), from the profiler trace of the traced window."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["tile_s"] <= 0:
        return None
    return tr["tile_s"] * 1e3 / rec["steps"]
