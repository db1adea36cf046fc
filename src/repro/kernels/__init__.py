"""Pallas TPU kernels (validated via interpret=True on CPU) + jnp oracles.

Each kernel package: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (implementations registered into `repro.api`'s KernelRegistry under
(op_name, "pallas"|"ref") plus a deprecated kwarg-compatible shim), ref.py
(pure-jnp oracle). New code should dispatch through `repro.api.ops` with an
ExecutionPolicy instead of these per-kernel entry points.
"""
from . import common  # noqa: F401
from .aio_matmul import aio_matmul  # noqa: F401
from .aio_quant import aio_quantize  # noqa: F401
from .depthwise import depthwise_conv  # noqa: F401
from .flash_attention import attention, chunked_attention, mha_ref  # noqa: F401
from .grouped_matmul import grouped_matmul, morphable_multi_gemm  # noqa: F401
