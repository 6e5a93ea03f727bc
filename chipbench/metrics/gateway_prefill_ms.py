"""Device busy milliseconds per gateway prefill: the device time inside
the engine's ``engine.prefill`` spans in the traced window, over their
number (``chipbench/engine_trace.py``)."""
from chipbench.engine_trace import device_ms_per_span


def read(rec):
    return device_ms_per_span(rec, "engine.prefill")
