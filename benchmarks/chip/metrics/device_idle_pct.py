"""Share of the traced window in which no operation ran on the chip: one
minus the union of the device's operation intervals over the window."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
