"""Arithmetic shared by the per-layer metric readers under
``chipbench/metrics``.  A reader gets the run's record,

    {"trace": trace_reduce.reduce(...) or None,
     "run": {"units", "flops_per_unit", "window_s", "compile_s", "chips",
             "peak_flops_per_s"}},

and returns its number, or None where the run gave nothing to read."""
from __future__ import annotations


def mfu(rec) -> float | None:
    """Model FLOPs of the units completed in the window over the window's
    length times the chips' bf16 peak, in percent."""
    run = rec["run"]
    if not run["units"]:
        return None
    achieved = run["units"] * run["flops_per_unit"] / run["window_s"]
    return 100.0 * achieved / (run["chips"] * run["peak_flops_per_s"])


def idle_percent(rec) -> float | None:
    """Share of the traced window in which no operation ran on the device."""
    tr = rec["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]


def device_ms_per_unit(rec) -> float | None:
    """Device busy time in the traced window per unit completed in it."""
    tr, run = rec["trace"], rec["run"]
    if tr is None or not run["units"]:
        return None
    return 1e3 * tr["busy_s"] / run["units"]
