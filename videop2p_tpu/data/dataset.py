"""The single-video dataset and Stage-2 frame loader.

Re-design of /root/reference/tuneavideo/data/dataset.py (``TuneAVideoDataset``)
and the Stage-2 ``load_512_seq`` (run_videop2p.py:413-440). The reference uses
decord for mp4 and PIL for image dirs; decord is not in this image, so mp4
decoding goes through imageio/OpenCV with the same frame-sampling semantics
(``sample_start_idx`` + ``sample_frame_rate`` stride, dataset.py:44-49).

Outputs are numpy channels-last float32: training clips (F, H, W, 3) in
[-1, 1] (dataset.py:55); Stage-2 sequences (F, S, S, 3) uint8 center-cropped
squares (run_videop2p.py:425-439).
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import List, Optional

import numpy as np
from PIL import Image

__all__ = ["SingleVideoDataset", "TokenDocument", "load_frame_sequence"]

_IMG_EXT = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


def _numeric_sort(names: List[str]) -> List[str]:
    """Sort '1.jpg', '2.jpg', … '10.jpg' numerically like the reference's
    ``sorted(key=lambda x: int(x[:-4]))`` (dataset.py:37), falling back to
    lexicographic for non-numeric stems."""

    def key(n):
        stem = os.path.splitext(n)[0]
        m = re.search(r"(\d+)$", stem)
        return (0, int(m.group(1)), n) if m else (1, 0, n)

    return sorted(names, key=key)


def _read_video_frames(path: str) -> List[np.ndarray]:
    """Decode every frame of a video file to RGB uint8 arrays."""
    try:
        import imageio.v3 as iio

        return [np.asarray(f) for f in iio.imiter(path)]
    except Exception:
        import cv2

        cap = cv2.VideoCapture(path)
        frames = []
        while True:
            ok, frame = cap.read()
            if not ok:
                break
            frames.append(cv2.cvtColor(frame, cv2.COLOR_BGR2RGB))
        cap.release()
        if not frames:
            raise IOError(f"could not decode any frames from {path!r}")
        return frames


def _load_dir_frames(path: str) -> List[np.ndarray]:
    names = _numeric_sort([n for n in os.listdir(path) if n.lower().endswith(_IMG_EXT)])
    if not names:
        raise IOError(f"no image frames in {path!r}")
    return [np.asarray(Image.open(os.path.join(path, n)).convert("RGB")) for n in names]


def _resize(frame: np.ndarray, width: int, height: int) -> np.ndarray:
    return np.asarray(Image.fromarray(frame).resize((width, height), Image.BICUBIC))


@dataclasses.dataclass
class SingleVideoDataset:
    """The one-clip training 'dataset' (``__len__ == 1``, dataset.py:41).

    ``video_path``: an mp4 file or a directory of numbered frames;
    sampling picks ``n_sample_frames`` starting at ``sample_start_idx`` with
    stride ``sample_frame_rate`` (dataset.py:44-49).
    """

    video_path: str
    prompt: str
    width: int = 512
    height: int = 512
    n_sample_frames: int = 8
    sample_start_idx: int = 0
    sample_frame_rate: int = 1

    def __len__(self) -> int:
        return 1

    def load(self) -> np.ndarray:
        """(F, H, W, 3) float32 in [-1, 1]."""
        if os.path.isdir(self.video_path):
            frames = _load_dir_frames(self.video_path)
        else:
            frames = _read_video_frames(self.video_path)
        idx = [
            self.sample_start_idx + i * self.sample_frame_rate
            for i in range(self.n_sample_frames)
        ]
        if idx[-1] >= len(frames):
            raise ValueError(
                f"sampling indices {idx} exceed the {len(frames)} available frames "
                f"of {self.video_path!r}"
            )
        picked = [_resize(frames[i], self.width, self.height) for i in idx]
        arr = np.stack(picked).astype(np.float32)
        return arr / 127.5 - 1.0  # (dataset.py:55)


@dataclasses.dataclass
class TokenDocument:
    """The one-document training 'dataset' of a token model (``__len__ ==
    1``): ``n_tokens`` token ids below ``vocab_size``. ``document_path`` is a
    ``.npy`` of integer ids (read flat, the first ``n_tokens`` taken); with
    no path the ids are drawn uniformly over the vocabulary from
    ``document_seed`` (no tokenizer ships)."""

    n_tokens: int
    vocab_size: int
    document_path: Optional[str] = None
    document_seed: int = 0

    def __len__(self) -> int:
        return 1

    def load(self) -> np.ndarray:
        """(n_tokens,) int32."""
        if self.document_path is None:
            rng = np.random.default_rng(self.document_seed)
            return rng.integers(0, self.vocab_size, self.n_tokens, dtype=np.int32)
        ids = np.load(self.document_path).reshape(-1)
        if not np.issubdtype(ids.dtype, np.integer) or len(ids) < self.n_tokens:
            raise ValueError(
                f"{self.document_path!r}: {len(ids)} ids of dtype {ids.dtype}; "
                f"{self.n_tokens} integer ids needed"
            )
        ids = ids[: self.n_tokens]
        if ids.min() < 0 or ids.max() >= self.vocab_size:
            raise ValueError(
                f"{self.document_path!r}: ids span [{ids.min()}, {ids.max()}], "
                f"outside the vocabulary held here (0..{self.vocab_size - 1})"
            )
        return ids.astype(np.int32)


def load_frame_sequence(
    path: str,
    size: int = 512,
    num_frames: Optional[int] = None,
    *,
    left: int = 0,
    right: int = 0,
    top: int = 0,
    bottom: int = 0,
) -> np.ndarray:
    """Stage-2 loader (``load_512_seq``, run_videop2p.py:413-440): sorted
    frames, optional edge crop, center-square crop, resize to ``size``².
    Returns (F, size, size, 3) uint8.

    Reference quirk replicated deliberately: its ``sampling_rate`` parameter
    only gates a length check and never strides the frames
    (run_videop2p.py:418-423, SURVEY §7 quirks) — here the knob is an honest
    ``num_frames`` head-truncation instead.
    """
    frames = _load_dir_frames(path)
    out = []
    for img in frames:
        h, w = img.shape[:2]
        img = img[top : h - bottom if bottom else h, left : w - right if right else w]
        h, w = img.shape[:2]
        if h < w:
            off = (w - h) // 2
            img = img[:, off : off + h]
        elif w < h:
            off = (h - w) // 2
            img = img[off : off + w]
        out.append(_resize(img, size, size))
    if num_frames is not None:
        out = out[:num_frames]
    return np.stack(out).astype(np.uint8)
