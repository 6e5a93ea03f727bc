"""Model FLOPs completed in the window over its length and the chip's
bf16 peak, in percent (``chipbench/flops.py`` counts the FLOPs)."""
from chipbench.readers import mfu


def read(rec):
    return mfu(rec)
