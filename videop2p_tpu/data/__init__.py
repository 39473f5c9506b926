"""Datasets and frame loading."""

from videop2p_tpu.data.dataset import (
    SingleVideoDataset,
    TokenDocument,
    load_frame_sequence,
)

__all__ = ["SingleVideoDataset", "TokenDocument", "load_frame_sequence"]
