"""Host milliseconds per greedy decision spent moving arguments to the
device: the runtime's ``DevicePut`` events (``chipbench/host_dispatch.py``)."""
from chipbench.host_dispatch import per_unit


def read(rec):
    ms = per_unit(rec, "phase_s", "host.put")
    return None if ms is None else 1e3 * ms
