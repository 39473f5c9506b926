"""On-chip A/B of the fused one-pass GroupNorm kernel vs the XLA two-pass
path, at the bench working point.

Standalone microbenchmarks carry a first-measurement bias; the
ground truth is in-forward op time from an xplane trace. This driver runs a
short cached fast edit (the headline program) once per GroupNorm
implementation, traces both, and prints the per-family device-time tables
side by side plus the wall-clock of the measured call.

Usage:
  PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python \
      PYTHONPATH=/root/repo python tools/bench_groupnorm.py [steps]
"""

from __future__ import annotations

import collections
import os
import re
import shutil
import sys
import tempfile
import time

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_REPO, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _family(op_name: str) -> str:
    n = op_name.lower()
    # the GN kernel is ALSO a Pallas custom call — it carries an explicit
    # name= (ops/groupnorm.py pallas_call) precisely so this A/B can split
    # it from the attention kernel's custom calls
    if "fused_group_norm" in n:
        return "groupnorm (kernel)"
    if "custom-call" in n or "attn" in n and "fusion" not in n:
        return "attn (custom-call)"
    if n.startswith("convert") or "convert" in n.split(".")[0]:
        return "convert"
    if n.startswith("copy"):
        return "copy"
    if "convolution" in n:
        return "convolution"
    if n.startswith("fusion") or re.match(r".*fusion", n.split(".")[0] or ""):
        return "fusion"
    if n.startswith("while"):
        return "while (wrapper)"
    return "other"


def run_one(group_norm: str, steps: int):
    import bench

    wp = bench.build_fast_edit_working_point(
        num_frames=8, num_steps=steps, cached=True, group_norm=group_norm
    )
    # warm on a different input than the traced call
    bench.hard_block(wp.e2e_cached(wp.params, wp.x_warm))
    tdir = tempfile.mkdtemp(prefix=f"gn_ab_{group_norm}_")
    opts = jax.profiler.ProfileOptions()
    opts.enable_hlo_proto = False
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.time()
    bench.hard_block(wp.e2e_cached(wp.params, wp.x0))
    wall = time.time() - t0
    jax.profiler.stop_trace()

    from profile_xplane import iter_device_events, module_device_span_seconds

    fams = collections.Counter()
    for name, ps in iter_device_events(tdir):
        fams[_family(name)] += ps
    span = module_device_span_seconds(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    del wp
    jax.clear_caches()
    return wall, span, {k: v / 1e12 for k, v in fams.items()}


def main() -> None:
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(__doc__.strip())
        return
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 50
    results = {}
    for impl in ("xla", "auto"):
        wall, span, fams = run_one(impl, steps)
        results[impl] = (wall, span, fams)
        print(f"\n=== group_norm={impl!r}: wall {wall:.3f}s, device span "
              f"{span:.3f}s ===")
        body = {k: v for k, v in fams.items() if k != "while (wrapper)"}
        total = sum(body.values())
        for fam, s in sorted(body.items(), key=lambda kv: -kv[1]):
            print(f"  {s:7.3f} s  {100 * s / max(total, 1e-9):5.1f} %  {fam}")

    if len(results) == 2:
        w_x, s_x, f_x = results["xla"]
        w_a, s_a, f_a = results["auto"]
        print(f"\nA/B at {steps} steps: xla {s_x:.3f}s → fused {s_a:.3f}s "
              f"device span ({100 * (s_x - s_a) / max(s_x, 1e-9):+.1f} % "
              f"saved); convert family "
              f"{f_x.get('convert', 0):.3f} → {f_a.get('convert', 0):.3f} s")


if __name__ == "__main__":
    main()
