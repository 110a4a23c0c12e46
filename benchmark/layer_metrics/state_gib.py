"""Program state: bytes of the persistable scope arrays (weights, Adam
moments, counters) on the fullest device after warm-up, in GiB."""


def read(record):
    return record["state_bytes"] / 2.0 ** 30
