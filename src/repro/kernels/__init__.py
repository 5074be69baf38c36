"""Pallas TPU kernels for the framework's compute hot-spots.

* ``sparton`` / ``sparton_bwd`` — the paper's fused LM head (fwd + bwd).
* ``attention`` — the backbone's fused attention (fwd + bwd), which
  keeps the scores on chip and skips blocks past each row's extent.
* ``topk_score`` — beyond-paper transfer: fused streaming top-k
  retrieval scoring (never materializes the (B, N) score matrix).
* ``ops`` — jit'd differentiable wrappers (``custom_vjp``).
* ``ref`` — pure-jnp oracles for the allclose sweeps.
* ``autotune`` — block-size selection: VMEM-budgeted candidate
  enumeration, timing, JSON winner cache.
"""

from repro.kernels import autotune
from repro.kernels.autotune import (autotune_blocks,
                                    autotune_kernel_blocks, get_blocks)
from repro.kernels.ops import sparton_head, sparton_lm_head_kernel
from repro.kernels.sparton import sparton_forward
from repro.kernels.sparton_bwd import (sparton_backward,
                                       sparton_backward_de,
                                       sparton_backward_dh)
from repro.kernels.topk_score import merge_topk, topk_score
