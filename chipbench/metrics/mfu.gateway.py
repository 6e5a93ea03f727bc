"""Model FLOPs of the window's prefill and decode tokens at this chip's
share of the model (``chipbench/flops_lm.py``) over the window's length and
the chip's bf16 peak, in percent."""
from chipbench.readers import mfu


def read(rec):
    return mfu(rec)
