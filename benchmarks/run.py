"""Benchmark driver: one function per paper table/figure.

Prints ``name,value,derived`` CSV rows (the grading contract).  The
roofline table reads the cached FD sweep (benchmarks/out/roofline.json,
produced by ``python benchmarks/roofline.py --compute`` in its own
512-device process); everything else runs live.

    PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced horizons (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. e1,e7")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bidding_bench, cluster_24h, e1_calibration,
                            e2_step_response, e3_ar4, e4_closed_loop,
                            e7_fr_latency, e8_multicountry, e9_reserve,
                            engine_bench, engine_fleet, roofline,
                            service_bench, workload_bench)
    from benchmarks.common import emit, write_csv, write_report
    from repro.obs import trace

    suite = [
        ("e1", lambda: e1_calibration.run()),
        ("e2", lambda: e2_step_response.run()),
        ("e3", lambda: e3_ar4.run()),
        ("e4", lambda: e4_closed_loop.run()),
        ("e7", lambda: e7_fr_latency.run()),
        ("e8", lambda: e8_multicountry.run(fast=args.fast)),
        ("e8_batched",
         lambda: e8_multicountry.run_batched_bench(fast=args.fast)),
        ("e9", lambda: e9_reserve.run(fast=args.fast)),
        ("engine", lambda: engine_bench.run(fast=args.fast)),
        ("workload", lambda: workload_bench.run(fast=args.fast)),
        ("bidding", lambda: bidding_bench.run(fast=args.fast)),
        ("engine_sharded",
         lambda: engine_bench.run_sharded(fast=args.fast)),
        ("service", lambda: service_bench.run(fast=args.fast)),
        ("fleet", lambda: engine_fleet.run(fast=args.fast)),
        ("fig4", lambda: cluster_24h.run(fast=args.fast)),
        ("roofline", lambda: roofline.emit_table()),
    ]
    only = set(args.only.split(",")) if args.only else None
    print("name,value,derived")
    failures = 0
    with trace.profile():  # opt-in device trace: REPRO_JAX_PROFILE_DIR
        for name, fn in suite:
            if only and name not in only:
                continue
            t0 = time.time()
            try:
                with trace.span(f"suite.{name}", fast=bool(args.fast)):
                    fn()
                emit(f"{name}.status", "ok", f"{time.time()-t0:.1f}s")
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                # emit() CSV-sanitises the interpolated exception text, so
                # commas/newlines in the message cannot fork the stream
                emit(f"{name}.status", f"FAIL {e}", "")
                failures += 1
    write_csv()
    path = write_report(fast=bool(args.fast), failures=failures,
                        only=sorted(only) if only else None)
    trace.get_tracer().export_jsonl(path.replace(".json", ".jsonl"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
