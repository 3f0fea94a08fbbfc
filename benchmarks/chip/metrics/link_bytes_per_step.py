"""Bytes staged host->device plus device->host per timestep over the
window: ``ChainStats.uploaded + downloaded``, raw bytes, an exact count."""


def read(rec):
    return rec["window_link_bytes"] / rec["steps"]
