"""Share of the HBM roofline the tile programs reach: the least time a
step's compulsory bytes take at the chip's published HBM bandwidth, over the
tile programs' device time per step.  Stencils do a few operations per
byte, so bandwidth bounds them; the compulsory bytes are counted by the
benchmark (``compulsory.py``), not taken from the program."""


def read(rec):
    tr, peaks = rec.get("trace"), rec.get("peaks")
    if not tr or not peaks or tr["tile_s"] <= 0:
        return None
    least_s = rec["compulsory_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (tr["tile_s"] / rec["steps"])
