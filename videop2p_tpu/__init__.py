"""videop2p_tpu — a TPU-native (JAX/XLA/Pallas/pjit) video editing framework.

Re-designed from scratch with the capabilities of the reference Video-P2P
codebase (emilycai99/Video-P2P): one-shot video tuning (Tune-A-Video style),
DDIM / null-text inversion, prompt-to-prompt attention-controlled editing, and
temporally-dependent (autoregressive) noise sampling — all expressed as pure
functions over pytrees so the hot paths compile under `jax.jit` / `pjit`.

Layout conventions (TPU-first, deliberately different from the torch reference):
  * videos / latents are channels-last: ``(batch, frames, height, width, chan)``
    — XLA's preferred conv layout on TPU. The reference uses ``(b, c, f, h, w)``
    (e.g. /root/reference/tuneavideo/pipelines/pipeline_tuneavideo.py:36-38);
    converters live in ``videop2p_tpu.utils.layout``.
  * diffusion loops are ``lax.scan``s, not Python loops.
  * attention control is a pure function threaded through the UNet forward —
    no monkey-patching, no hidden counters
    (cf. /root/reference/ptp_utils.py:188-255).
"""

import time as _time

# where the span `process.import` starts (obs/spans.py): the package's first line
IMPORT_NS = _time.time_ns()

__version__ = "0.1.0"
