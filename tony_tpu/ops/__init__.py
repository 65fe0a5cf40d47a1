"""Compute ops: Pallas TPU kernels + XLA references (the hot path)."""

from tony_tpu.ops.attention import attention_reference, flash_attention, mha, repeat_kv  # noqa: F401
from tony_tpu.ops.quant import QTensor, dequantize, int8_matmul, quantize_int8, quantize_tree  # noqa: F401
from tony_tpu.ops.layers import (  # noqa: F401
    apply_rope,
    chunked_cross_entropy_loss,
    cross_entropy_loss,
    gelu_mlp,
    layer_norm,
    rms_norm,
    rope_frequencies,
    swiglu,
)
