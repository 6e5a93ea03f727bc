"""Device busy milliseconds per twin call (one episode horizon of the
fleet) in the traced window."""
from chipbench.readers import device_ms_per_unit


def read(rec):
    return device_ms_per_unit(rec)
