"""paddle_tpu.ops — custom Pallas TPU kernels.

The reference's equivalent is the C++/CUDA operator library
(/root/reference/paddle/fluid/operators/); here the op library is the XLA
op set (paddle_tpu.tensor / nn.functional lowerings), and this package
holds only the kernels XLA won't produce on its own: fused attention
(flash, decode, paged), the grouped product over held experts, the KDA
scan's chunk kept in VMEM (``kda_scan`` dispatches to
``kda_chunk_kernel`` on the chip), power retention's decode step over a
state updated where it lies (``power_retention`` dispatches to
``power_retention_kernel``), the blocked cross-entropy and the
quantized products.  ``ssd_scan`` is XLA's chunked form alone (a kernel
lost to it), and so is its single-token form ``ssd_step`` (a kernel lost
to that too).  Every entry point with two paths records the one it traced
in ``kernel_paths``.
"""
from . import kernel_paths  # noqa: F401
from .flash_attention import (  # noqa: F401
    flash_attention, flash_attention_available, get_block_sizes,
    set_interpret_mode)
from .decode_attention import (  # noqa: F401
    chunk_prefill_attention, decode_attention,
    decode_attention_available, decode_attention_window,
    decode_attention_writes, write_decode_attention,
    paged_chunk_prefill_attention, paged_decode_attention,
    paged_decode_attention_available, paged_decode_attention_window,
    write_kv)
from .fused_cross_entropy import (  # noqa: F401
    fused_linear_cross_entropy, pick_vocab_block)
from .grouped_matmul import (  # noqa: F401
    grouped_matmul, grouped_matmul_available)
from .ssd_scan import (  # noqa: F401
    causal_conv1d, causal_conv1d_step, conv_window, ssd_scan,
    ssd_scan_with_state, ssd_step)
from .kda_scan import kda_scan  # noqa: F401
from .power_retention import (  # noqa: F401
    power_retention_chunked, power_retention_step)
from .quantized_matmul import (  # noqa: F401
    quantized_matmul, quantized_matmul_available, fake_quant_matmul,
    quantize_channel, quantize_kv, dequantize_kv, get_qmm_tiles)
