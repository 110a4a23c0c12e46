"""Device: 1 - device-busy time over the traced window, mean over chips."""


def read(record):
    traced = record.get("traced")
    return 100.0 * traced["idle_share"] if traced else None
