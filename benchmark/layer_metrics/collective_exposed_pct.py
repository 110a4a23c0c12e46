"""Collectives: time a collective operation ran on a chip while no other
operation ran there, over the traced window; mean over chips, in %."""


def read(record):
    traced = record.get("traced")
    if not traced:
        return None
    return 100.0 * traced["exposed_collective_s"] / traced["window_s"]
