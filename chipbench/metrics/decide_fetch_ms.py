"""Host milliseconds per greedy decision spent waiting for and copying
the results back: the runtime's ``ArrayImpl.copy_to_host_async`` and
``np.asarray(jax.Array)`` events (``chipbench/host_dispatch.py``)."""
from chipbench.host_dispatch import per_unit


def read(rec):
    ms = per_unit(rec, "phase_s", "host.fetch")
    return None if ms is None else 1e3 * ms
