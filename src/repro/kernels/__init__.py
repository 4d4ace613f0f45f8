"""Pallas TPU kernels for the performance-critical compute layers.

Paper hot spots:
  * ``seg_sum``       — blocked NA aggregation (gather + weighted segment sum)
                        via the one-hot-matmul idiom (MXU has no scatter);
                        consumes the Graph Restructurer's banded edge blocks.
  * ``edge_softmax``  — per-destination online-softmax statistics over edge
                        blocks (flash-attention-style m/s accumulation).
  * ``spgemm_bsr``    — block-sparse boolean SpGEMM for the SGB stage
                        (tile-occupancy pruning replaces CSR SpGEMM on MXU).

LM-zoo hot spots:
  * ``flash_attention`` — block-wise attention with causal / sliding-window /
                          logit-softcap / GQA support.
  * ``ssd_scan``        — Mamba2 SSD chunked state passing.

Every kernel has a pure-jnp oracle in ``ref.py`` and a jit'd public wrapper
in ``ops.py``.  Kernels are TPU-targeted (pl.pallas_call + BlockSpec VMEM
tiling) and validated on CPU in Pallas interpret mode; ``backend.py`` picks
the mode from the platform.
"""
