"""Host milliseconds per greedy decision spent launching the executable:
the runtime's ``CommonPjRtLoadedExecutable::Execute`` events
(``chipbench/host_dispatch.py``)."""
from chipbench.host_dispatch import per_unit


def read(rec):
    ms = per_unit(rec, "phase_s", "host.launch")
    return None if ms is None else 1e3 * ms
