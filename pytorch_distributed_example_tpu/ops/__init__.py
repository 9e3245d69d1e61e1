"""Pallas TPU kernels for the framework's hot ops (flash attention for
training, paged decode and chunk attention over the serve block pool, the
decode step's gated delta rule over the pool of recurrent state blocks, the
sparse MLP's three grouped products as one kernel: `grouped_mlp.py`, reached
through `parallel/expert_parallel.py::grouped_swiglu`) — plus the
jnp-level block-scaled quantization codec (`quant.py`) shared by the
quantized collectives and the int8 paged KV cache."""

from . import quant  # noqa: F401
from .delta_recurrence import delta_kernel_ok, paged_delta_step  # noqa: F401
from .flash_attention import (  # noqa: F401
    flash_attention,
    gather_paged_kv,
    paged_window_span,
    partitioned_over,
)
from .paged_attention import (  # noqa: F401
    gather_paged_latent,
    latent_chunk_attention,
    latent_decode_attention,
    paged_chunk_attention,
    paged_decode_attention,
    paged_kernel,
    pool_head_pack,
    pool_kv_heads,
    pool_kv_shape,
    pool_latent_width,
)
from .quant import (  # noqa: F401
    dequantize_blockwise,
    dequantize_kv,
    quantize_blockwise,
    quantize_kv,
    quantized_all_reduce,
)
from .reference import dense_attention  # noqa: F401
