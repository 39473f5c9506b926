"""Hot-path kernels: the fused Pallas pair and the chunked / dense fallbacks
for the spatial frame attention."""

from videop2p_tpu.ops.attention import (
    chunked_frame_attention,
    dense_frame_attention,
    fused_bwd_block,
    fused_frame_attention,
    make_frame_attention_fn,
    training_frame_attention,
)

__all__ = [
    "chunked_frame_attention",
    "dense_frame_attention",
    "fused_bwd_block",
    "fused_frame_attention",
    "make_frame_attention_fn",
    "training_frame_attention",
]
