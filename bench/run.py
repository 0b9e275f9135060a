#!/usr/bin/env python3
"""The benchmark of the served TGN path: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``: graph shape, widths, tenant lanes, the
limits of the check) and a traffic mix (``bench/traffic/<name>.json``).
The run builds the cell from ``--seed`` (graph, weights made on the
device, every tenant attached), warms up every shape its window uses,
measures ``--seconds``, drains, and replays everything the window served
through the plain float32 reference (``bench/reference.py``).

Earlier lines report; the last lines of standard error give each number
compared beside its limit; the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``. The run refuses, with no result, any platform but a TPU, fewer
chips than the cell asks for, and a checkout without the program
(``src/repro``).
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS:8.2f} s] {msg}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also compare the reference at 'high' precision "
                         "in the program's place (the check's control; the "
                         "benchmark's own runs leave it off)")
    args = ap.parse_args(argv)
    # a run that is stopped from outside says where it was, and one that
    # hangs ends itself inside the first run's allowance
    faulthandler.register(signal.SIGTERM, chain=True)
    faulthandler.dump_traceback_later(1150, exit=True)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench/run.py: the program (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import harness
    spec = harness.load_spec()
    cell, _cfg, _traffic = harness.resolve(spec, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench/run.py: needs a TPU, JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench/run.py: {args.workload} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.utils import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device_kind {devices[0].device_kind!r}, {len(devices)} "
        f"device(s); compile cache {cache}")

    trace_dir = (tempfile.mkdtemp(prefix="bench_trace_") if args.trace
                 else None)
    try:
        rec = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS, log,
                               control=args.control, trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    for lane, e in rec["errors"].items():
        log(f"check {lane}: {json.dumps(e)}")
    for lane, e in rec.get("control_errors", {}).items():
        log(f"control {lane}: {json.dumps(e)}")
    for name, c in rec.get("control_checks", {}).items():
        log(f"control check {name} {c['value']} (limit {c['limit']})")
    if "control_correct" in rec:
        log(f"control correct: {rec['control_correct']}")
    log(f"reference replay {rec['reference_s']} s; {rec['window_rounds']} "
        f"rounds in the window; {rec['events']} events applied")
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"],
           "metrics": harness.per_layer(spec, rec) if args.trace
           else harness.end_to_end(spec, rec),
           "device": rec["device"]}
    if args.trace:
        tr = rec["trace"]
        out["breakdown"] = {"device_ops": harness.trace_reduce.top(tr["ops"]),
                            "idle_gaps": harness.trace_reduce.top(tr["gaps"])}
    out["checks"] = rec["checks"]
    for name, c in rec["checks"].items():
        print(f"{name} {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
