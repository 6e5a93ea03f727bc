"""Pallas TPU kernels for the compute hot-spots (validated interpret=True on
CPU): flash_attention (prefill/train attention), mla_decode (absorbed-MLA
decode attention over each slot's live cache rows, the path every MLA
model decodes through), ssd_scan (Mamba2 chunked SSD), ddpm_step (fused
D3PG reverse-diffusion update).  ``ops`` holds the jit'd public wrappers;
``ref`` the pure-jnp oracles (``mla_decode_ref``, the XLA absorbed form,
is only the tests' oracle)."""
from . import ops, ref  # noqa: F401
