"""The chip's ``peak_bytes_in_use`` after the window, as its allocator
reports it: what one step of this cell holds on the device at most."""


def read(rec):
    return rec.get("peak_hbm_bytes")
