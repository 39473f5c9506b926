#!/usr/bin/env python3
"""The benchmark's one command: runs ONE cell once, in one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name: the cell in ``BENCHMARK.json``, its file under
``benchmark/workloads/``, its configuration under ``benchmark/configs/``, its
driver under ``benchmark/drivers/``, and (``--trace 1``) one reader per
per-layer metric under ``benchmark/layer_metrics/``. Adding a cell, a
configuration or a metric is adding files and manifest entries only.

No TPU, or fewer chips than the cell asks for: exit 2, nothing on stdout.
``--rehearse`` drives the same control flow at tiny size on any backend; it
exits 3 and can never print a result line. No child process is started and
``JAX_PLATFORMS`` is not set here."""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, for setup_s

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def note(record: dict) -> None:
    """A progress record on stderr (never stdout: its last line is the
    result)."""
    record = dict(record, t_s=round(time.perf_counter() - T0, 1),
                  host_max_rss_gib=round(resource.getrusage(
                      resource.RUSAGE_SELF).ru_maxrss / 2 ** 20, 2))
    print("[bench] " + json.dumps(record, default=str), file=sys.stderr,
          flush=True)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, workload: str, bench_dir: str = HERE):
    """(manifest entry, cell file, configuration entry, configuration file)
    for a cell's name — by name only."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    entry = cells[workload]
    cell = load_json(os.path.join(bench_dir, "workloads", workload + ".json"))
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    config = load_json(os.path.join(os.path.dirname(bench_dir),
                                    cfg_entry["file"]))
    return entry, cell, cfg_entry, config


def find_reader(metric: str, bench_dir: str = HERE):
    """``layer_metrics/<metric>.py``, else the family's file
    (``unet_mfu.edit`` → ``unet_mfu.py``)."""
    base = os.path.join(bench_dir, "layer_metrics")
    for stem in (metric, metric.rsplit(".", 1)[0]):
        path = os.path.join(base, stem + ".py")
        if os.path.isfile(path):
            return load_module(path)
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                            f"under {base}")


def metrics_for(manifest: dict, section: str, workload: str, reported) -> list:
    """The metrics of one section that this cell reports: those that list it
    under ``workloads``, or list nothing and (per-layer) move an end-to-end
    metric the cell reports."""
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m.get("moves") in reported:
            out.append(m)
    return out


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny-size control-flow rehearsal on any backend; "
                         "exits 3, never prints a result line")
    return ap


def prepare(args):
    """Everything before the driver runs: the cell by name, the look for the
    chips, the compile cache. Returns ``(manifest, entry, driver, ctx)``, or
    an exit code where nothing may run."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, cell, cfg_entry, config = find_cell(manifest, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])
    if not os.path.isdir(os.path.join(ROOT, "videop2p_tpu")):
        print("benchmark: the program (videop2p_tpu/) is not in this "
              "checkout. Nothing was run.", file=sys.stderr)
        return 2
    driver = load_module(os.path.join(HERE, "drivers", cell["driver"] + ".py"))
    out_dir = os.path.join(ROOT, "outputs", "bench", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    # the TPU runtime's own logs go inside the checkout, not to /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))

    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < entry["chips"]):
        print(f"benchmark: {args.workload} needs {entry['chips']} TPU "
              f"chip(s); jax found {len(devices)} x "
              f"{devices[0].platform!r} ({devices[0].device_kind}). "
              "Nothing was run.", file=sys.stderr)
        return 2
    devices = devices[:entry["chips"]]

    from videop2p_tpu.cli.common import enable_compile_cache

    from benchmark.harness.result import CacheCounter, device_record

    # JAX_COMPILATION_CACHE_DIR if set, else the fixed <checkout>/.jax_cache
    cache_dir = enable_compile_cache()
    # no eviction: one UNet-scale executable is 100 MB and more, and a cache
    # that evicts (JAX_COMPILATION_CACHE_MAX_SIZE was 192 MiB on the chip's
    # machine) makes every run of the tune cell compile again
    jax.config.update("jax_compilation_cache_max_size", -1)
    ctx = {
        "t0": T0, "root": ROOT, "out_dir": out_dir, "cell": cell,
        "cell_name": args.workload, "config": config, "seed": args.seed,
        "seconds": seconds, "trace": bool(args.trace),
        "rehearse": args.rehearse, "devices": devices,
        "cache": CacheCounter(), "note": note,
    }
    note({"phase": "start", "workload": args.workload, "seed": args.seed,
          "seconds": seconds, "trace": args.trace, "jax": jax.__version__,
          "device": device_record(devices), "compile_cache_dir": cache_dir,
          "compile_cache_entries":
              len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0})
    return manifest, entry, driver, ctx


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    prepared = prepare(args)
    if isinstance(prepared, int):
        return prepared
    manifest, entry, driver, ctx = prepared
    cell, config = ctx["cell"], ctx["config"]

    from benchmark.harness.result import emit_result

    # the driver: set-up, the window, and what the window produced. It
    # returns after the window has closed and memory_peak_bytes has been
    # read, with the program's state freed; ``check`` then runs the reference.
    # (the program's own progress prints go to stderr: stdout carries the
    # result line and nothing else)
    with contextlib.redirect_stdout(sys.stderr):
        run = driver.run(ctx)
        note({"phase": "window_closed", **run["summary"]})
        device = run["device"]
        compared = run["check"]()
    note({"phase": "checked", "compile_cache": ctx["cache"].snapshot()})

    reported = {m["name"] for m in metrics_for(
        manifest, "end_to_end", args.workload, set())}
    metrics, breakdown = {}, None
    if args.trace:
        trace = run.get("trace") or {}
        if trace:
            device = dict(device, busy_s=trace["busy_s"],
                          window_s=trace["window_s"])
            breakdown = trace.get("breakdown")
        for m in metrics_for(manifest, "per_layer", args.workload, reported):
            reader_ctx = {"metric": m["name"], "cell": cell,
                          "config": config, "window": run["window"],
                          "trace": trace, "device": device}
            try:
                value = find_reader(m["name"]).read(reader_ctx)
            except KeyError:
                if not args.rehearse:  # an unknown chip is an error
                    raise
                value = None  # the CPU has no published peaks
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_for(manifest, "end_to_end", args.workload, set()):
            if m["name"] in run["end_to_end"]:
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    return emit_result(device=device, chips=entry["chips"],
                       attempted=run["attempted"], failed=run["failed"],
                       metrics=metrics, compared=compared,
                       breakdown=breakdown, rehearse=args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
