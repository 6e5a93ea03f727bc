"""Bytes a gateway decode step must read (``chipbench/flops_lm.py``:
every weight but the embedding table, the held experts its picks chose,
the latent cache its tokens attend), averaged over the window's steps,
over the step's device time (``gateway_step_ms``), as a share of the
chip's HBM bandwidth, in percent."""
from chipbench.engine_trace import device_ms_per_span


def read(rec):
    run = rec["run"]
    ms = device_ms_per_span(rec, "engine.decode_step")
    if ms is None or not run.get("hbm_bytes_per_s"):
        return None
    return 100.0 * run["decode_bytes_per_step"] / (1e-3 * ms) / run["hbm_bytes_per_s"]
