"""Pallas TPU kernels for the compute hot spots.

- coded_matmul: fused LT-encode + block matmul — the paper's own hot spot
  (helpers computing fountain-coded sub-matrix products) adapted to the MXU.
- lt_encode: standalone gather-accumulate encoder (coded gradient parities).
- block_combine: the collector's recovery, each source block summed on the
  VPU from the received blocks its decode matrix names
  (``core.fountain.apply_decoder``).
- flash_attention: tiled online-softmax attention (causal / sliding-window /
  logit-softcap / GQA) — the serving & training hot spot of the assigned
  architectures.

All kernels are TPU-targeted (pl.pallas_call + BlockSpec VMEM tiling) and
validated on CPU with interpret=True against the pure-jnp oracles in each
package's ref.py.  The jnp fallbacks (ops.py, use_pallas=False) are what the
CPU dry-run lowers.
"""

from . import coded_matmul, flash_attention, lt_encode  # noqa: F401
