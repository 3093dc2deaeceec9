"""The benchmark's entry point: one process, one cell, one run.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration and traffic are found by the names in
``BENCHMARK.json`` (``benchmarks/configs/<config>.json``,
``benchmarks/traffic/<traffic>.json``); the traffic file's ``kind`` picks the
runner.  With ``--trace 0`` the last line of stdout carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, each read by
``benchmarks/layer_metrics/<name>.py``.  Every earlier line is one JSON object
of detail (set-up parts, the window, the check).

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  ``--rehearse`` is the separate path for the
sandbox: the same code at the tiny sizes the data files give under
``rehearse``, on the CPU with the kernels interpreted, every line labelled
``"rehearsal": true`` and no metric printed under the result's ``metrics``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="python3 benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; proves the harness, "
                         "measures nothing")
    ap.add_argument("--spec", default=None,
                    help="another file in BENCHMARK.json's format (for "
                         "rehearsing a cell before it is added)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    from benchmarks.lib import harness
    spec = harness.benchmark_spec(args.spec)
    cell, config, traffic = harness.load_cell(spec, args.workload,
                                              args.rehearse)
    chips = int(cell["chips"])
    # a second compile of a compile-once entry raises instead of warning
    os.environ["PADDLE_TPU_STRICT_COMPILE"] = "1"

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", chips)
    elif jax.default_backend() != "tpu":
        sys.exit("benchmark: no TPU: the jax backend here is %r.  The "
                 "benchmark measures on the chip; only --rehearse runs on a "
                 "CPU, and reports no metric." % jax.default_backend())
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit("benchmark: workload %s needs %d chips, jax shows %d"
                 % (args.workload, chips, len(devices)))
    import paddle_tpu  # noqa: F401  (missing outside a full checkout: exit 1)
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    run = harness.Run(args, T_PROCESS, args.rehearse)
    run.record.update(chips=chips, rehearsal=args.rehearse)
    run.part("import")
    run.emit(phase="start", workload=args.workload, seed=args.seed,
             seconds=args.seconds, trace=args.trace, kind=traffic["kind"],
             compile_cache_dir=cache_dir, jax=jax.__version__,
             device_kind=devices[0].device_kind, devices=len(devices))
    if not args.rehearse:
        from benchmarks.lib import peaks
        peaks.peaks(devices[0].device_kind)   # an unknown part fails here

    if traffic["kind"] == "train":
        from benchmarks.lib import train as runner
    elif traffic["kind"] in ("serve_open", "serve_closed"):
        from benchmarks.lib import serve as runner
    else:
        sys.exit("benchmark: unknown traffic kind %r" % traffic["kind"])
    scope = (fa.interpret_scope() if args.rehearse
             else contextlib.nullcontext())
    with scope:
        registry, trace = runner.run(run, config, traffic, devices)

    record = run.record
    if args.trace:
        names = harness.metric_names(spec, "per_layer", args.workload)
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
        values = {}
        for name in names:
            value = harness.layer_reader(name)(registry, trace, record)
            if value is not None:
                values[name] = value
    else:
        names = harness.metric_names(spec, "end_to_end", args.workload)
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        values = dict(record["end_to_end"], setup_s=record["setup_s"])
        values = {k: values[k] for k in names}
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": {k: {"value": float(v), "unit": units[k]}
                          for k, v in values.items()},
              "device": dict(record["device"])}
    if args.trace and not trace["devices"] and not args.rehearse:
        sys.exit("benchmark: the traced run saw no operation on the device")
    if args.trace and trace["devices"]:     # a CPU rehearsal's holds none
        from benchmarks.lib import trace as trace_mod
        busy, window = trace_mod.busy_and_window(trace)
        result["device"].update(busy_s=busy, window_s=window)
        result["breakdown"] = {
            "device_ops": trace_mod.top_device_ops(trace, 10),
            "idle_gaps": trace_mod.idle_gaps(trace, 10)}
        run.emit(phase="trace", modules=trace_mod.module_summary(trace),
                 host_spans=len(trace["host"]),
                 lines_seen=trace["lines_seen"])
    if args.rehearse:
        # a CPU run yields no device metric: the names stay, the values go
        result["metrics"] = {}
        result["rehearsed_metric_names"] = sorted(values)
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
