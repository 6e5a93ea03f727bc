"""Device busy milliseconds per greedy decision in the traced window."""
from chipbench.readers import device_ms_per_unit


def read(rec):
    return device_ms_per_unit(rec)
