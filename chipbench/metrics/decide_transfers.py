"""Arrays moved between host and device per greedy decision: the
runtime's ``DevicePut`` and ``np.asarray(jax.Array)`` events
(``chipbench/host_dispatch.py``)."""
from chipbench.host_dispatch import per_unit


def read(rec):
    return per_unit(rec, "transfers")
