"""Driver: closed-loop served edits through the normal entry point.

The engine exactly as ``python -m videop2p_tpu.cli.serve`` builds it
(``serve_cli.build_engine``), behind ``serve/http.make_server`` in this
process, through ``serve/client.EngineClient`` — the arrangement of
``chip_smoke.phase_serve``. Set-up sends the cell's one unmeasured request
(it inverts, fills the store, compiles or loads this cell's shapes only).
In the window each of ``clients`` (1 here) submits its next request when the
last completes, until ``--seconds`` have passed; the in-flight one finishes
and counts."""

from __future__ import annotations

import os
import shutil
import time


def run(ctx: dict) -> dict:
    import jax
    import numpy as np

    from videop2p_tpu.cli import serve as serve_cli
    from videop2p_tpu.serve.client import EngineClient
    from videop2p_tpu.serve.http import make_server

    from benchmark.harness import steer, trace, traffic
    from benchmark.harness.result import device_record

    cell, config, note = ctx["cell"], ctx["config"], ctx["note"]
    if cell.get("clients", 1) != 1:
        raise NotImplementedError("this driver runs one closed-loop client")
    # the weights come from the cell's own ``weights_seed`` and --seed only
    # orders the traffic: the engine's GIF writing takes 2.1-2.9 s an edit
    # depending on what the frames hold, so weights from --seed made edit_s
    # differ by 7 % between seeds and by 0.1 % between two runs of one seed
    # (my chip runs, PR 25). Every seed now serves the same model.
    weights_seed = int(cell.get("weights_seed", ctx["seed"])) % (2 ** 31 - 1)
    if not ctx["rehearse"]:
        steer.cut_depth(config)
    steer.seeded_weights()

    out_dir = os.path.join(ctx["out_dir"], "serve")
    shutil.rmtree(out_dir, ignore_errors=True)
    argv = list(cell["rehearse_engine_args" if ctx["rehearse"]
                     else "engine_args"])
    argv += ["--out_dir", out_dir, "--port", "0", "--seed", str(weights_seed)]
    args = serve_cli.build_parser().parse_args(argv)
    engine = serve_cli.build_engine(args)
    engine.keep_videos = True  # the check compares what was served
    server = make_server(engine, host=args.host, port=args.port).start()
    client = EngineClient(server.url, timeout_s=30.0)
    note({"phase": "engine_built", "argv": argv})

    def serve_one(request: dict) -> dict:
        t1 = time.perf_counter()
        rid = client.submit(request)
        rec = client.result(rid, wait_s=1100.0)
        t2 = time.perf_counter()
        rec["client_s"] = t2 - t1
        rec["t_submit"], rec["t_done"] = t1, t2
        return rec

    must = cell["must_hold"]

    def holds(rec: dict) -> bool:
        return all(rec.get(k) == v for k, v in must.items())

    records, trace_info = [], None
    try:
        assert client.healthz().get("ok"), "engine not healthy"
        first = serve_one(traffic.setup_request(cell, ctx["root"]))
        assert first["status"] == "done" and first["src_err"] == 0.0, first
        assert not first["store_hit"], "the set-up request hit the store"
        engine.take_videos(first["id"])
        note({"phase": "setup_request", "client_s": round(first["client_s"], 3),
              "dispatch_s": first.get("dispatch_s"),
              "compile_events": first.get("compile_events"),
              "compile_cache": ctx["cache"].snapshot()})

        stream = traffic.edit_requests(cell, ctx["root"], ctx["seed"])
        cache0 = ctx["cache"].snapshot()
        t_start = time.perf_counter()
        setup_s = t_start - ctx["t0"]
        while time.perf_counter() - t_start < ctx["seconds"]:
            records.append(serve_one(next(stream)))
        t_end = time.perf_counter()
        cache_in_window = ctx["cache"].since(cache0)
        if ctx["trace"]:
            # the window has closed: a few more whole requests under the
            # profiler (host gaps between them included) for the device's
            # side of the per-layer metrics
            trace_dir = os.path.join(ctx["out_dir"], "trace")
            trace.start(trace_dir)
            t_trace0 = time.perf_counter()
            traced = [serve_one(next(stream))
                      for _ in range(int(cell.get("traced_requests", 2)))]
            trace_info = {"dir": trace_dir, "requests": len(traced),
                          "window_s": time.perf_counter() - t_trace0}
            trace.stop()
            note({"phase": "traced_requests", "hold": [holds(r) for r in traced],
                  "client_s": [round(r["client_s"], 3) for r in traced]})
    finally:
        server.close()
        engine.close(drain_s=args.drain_s)

    window_s = t_end - t_start
    done = [r for r in records if holds(r)]
    failed = len(records) - len(done)
    if cache_in_window["writes"]:
        # a compile inside the window: every request of it is suspect
        note({"phase": "COMPILE_IN_WINDOW", **cache_in_window})
        failed = len(records)
    end_to_end = {"setup_s": setup_s}
    if done:
        end_to_end["edit_s"] = window_s / len(done)

    # what the check needs, then free the program's state
    rng = np.random.default_rng(int(ctx["seed"]))
    n_check = min(int(cell.get("checked_requests", 2)), len(done))
    picked = sorted(rng.choice(len(done), size=n_check, replace=False)) \
        if n_check else []
    samples = []
    for i in picked:
        rec = done[int(i)]
        samples.append({"id": rec["id"], "request": rec["request"],
                        "videos": engine.videos(rec["id"])})
    key = done[0].get("store_key") if done else None
    products = engine.store.get(key) if key else None
    anchor = np.asarray(jax.device_get(products[1])) if products else None
    bundle = engine.programs.bundle
    vae_params = bundle.vae_params
    device = device_record(ctx["devices"])
    window = {
        "kind": "serve", "window_s": window_s, "requests": [
            {k: r.get(k) for k in ("client_s", "dispatch_s", "total_s",
                                   "queue_wait_s", "resolve_s", "store_hit",
                                   "compile_events", "src_err", "status")}
            for r in records],
        "completed": len(done), "cache_in_window": cache_in_window,
        "frames": int(args.video_len), "steps": int(args.steps),
        "memory_peak_bytes": device["memory_peak_bytes"],
        "traced_forwards": (
            trace_info["requests"] * int(args.steps)
            * config["inference"]["unet_forwards_per_step"]
            if trace_info else None),
    }
    del products, bundle, records
    engine._videos.clear()
    del engine, server, client
    steer.free_program_state()

    traced = None
    if trace_info:
        traced = trace.reduce(trace_info["dir"], trace_info["window_s"],
                              len(ctx["devices"]),
                              allow_empty=ctx["rehearse"])
        shutil.rmtree(trace_info["dir"], ignore_errors=True)

    def check() -> dict:
        from benchmark.reference import serve_check

        limits = cell["limits"]
        compared = {
            "requests_not_holding": {
                "value": failed, "limit": limits["requests_not_holding"]},
            "src_err_max": {
                "value": max((r["src_err"] for r in window["requests"]
                              if r["src_err"] is not None), default=None),
                "limit": limits["src_err_max"]},
        }
        compared.update(serve_check.compare(
            samples=samples, anchor=anchor, vae_params=vae_params,
            config=config, limits=limits, rehearse=ctx["rehearse"],
            note=note))
        return compared

    return {
        "attempted": len(window["requests"]), "failed": failed,
        "end_to_end": end_to_end, "window": window, "device": device,
        "trace": traced, "check": check,
        "summary": {"window_s": round(window_s, 3),
                    "completed": len(done), "failed": failed,
                    "setup_s": round(setup_s, 2),
                    "cache_in_window": cache_in_window,
                    "client_s": [round(r["client_s"], 3)
                                 for r in window["requests"]],
                    "dispatch_s": [r["dispatch_s"]
                                   for r in window["requests"]]},
    }
