"""Render a self-contained HTML edit report from a run ledger + sidecar.

Usage:  python tools/edit_report.py <ledger.jsonl> [-o report.html]
                                    [--sidecar obs_sidecar.npz]

Renders the LAST run of the ledger (ledger files append across
invocations): per-word cross-attention heatmap grids across DDIM steps,
LocalBlend mask overlays on the edited frames, the null-text loss
sparkline, the edit-quality table (PSNR/SSIM), the "Where time goes"
section (execute-latency distributions + device-trace breakdowns —
``trace`` events whose directory still exists are auto-mined with the
stdlib xplane reader, no tensorflow), and the regression verdicts —
everything base64-embedded in one HTML file. The sidecar ``.npz`` is
located from the ledger's ``attn_maps``/``quality`` events when not
given explicitly.

stdlib + numpy only (tests/test_ledger_schema.py pins the import closure)
— runs on any box the ledger was copied to, no plotting stack, no
accelerator, no repo checkout beyond this package.
"""

from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from videop2p_tpu.obs.report import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
