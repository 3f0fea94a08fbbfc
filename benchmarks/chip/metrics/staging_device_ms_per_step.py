"""Device milliseconds per timestep of programs other than the tile
programs (the data plane's eager staging ops: slot zeros, ``.at[].set``
copies, slices), from the profiler trace of the traced window."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["staging_s"] <= 0:
        return None
    return tr["staging_s"] * 1e3 / rec["steps"]
