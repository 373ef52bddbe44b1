"""Pallas TPU kernels: fused batch-norm (``kernels.py``; reference
``src/operator/nn/batch_norm.cu``), the flash attention forward and backward
(``attention.py``), the routed layer's grouped products (``grouped.py``) and
the Mamba-2 scan with its backward (``ssd.py``).  Each has an
interpreter-mode test against its jnp oracle in ``dt_tpu.ops``.
"""

from dt_tpu.ops.pallas.kernels import (
    fused_bn_inference as fused_bn_inference,
)
