"""Find the highest arrival rate an open-loop cell sustains: made once, by
hand, on the chip; the driver never runs it.

    python3 benchmarks/sweep.py --workload serve_c13b_chat --seed 11 \
        --seconds 20 --rates 2,2.5,3.1,3.9,4.9,6.1

One process, one server; every rate is offered for ``--seconds`` with the
cell's own lengths, and every stream is waited for before the next rate
starts.  One JSON line per rate.  The knee is the highest rate with nothing
shed or failed and no more requests in flight at the end of its step than at
the middle; the cell's ``rate_rps`` is 0.8 x the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python3 benchmarks/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests a second, ascending")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--spec", default=None,
                    help="another file in BENCHMARK.json's format")
    args = ap.parse_args(argv)
    args.trace = 0
    from benchmarks.lib import harness
    spec = harness.benchmark_spec(args.spec)
    _, config, traffic = harness.load_cell(spec, args.workload,
                                           args.rehearse)
    if traffic["kind"] != "serve_open":
        sys.exit("sweep: %s is not an open-loop cell" % args.workload)
    os.environ["PADDLE_TPU_STRICT_COMPILE"] = "1"
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("sweep: no TPU (backend %r)" % jax.default_backend())
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    from benchmarks.lib import serve
    enable_compile_cache()
    run = harness.Run(args, T_PROCESS, args.rehearse)
    run.record.update(chips=1, rehearsal=args.rehearse)
    stack = serve.Stack(run, config, traffic)
    try:
        serve.warm_up(run, stack, traffic, config["token_id_limit"])
        run.setup_done()
        base_seed = args.seed
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            # its own prompts at every rate: a prompt seen at the last rate
            # would be served from the engine's prefix cache
            args.seed = base_seed + i
            part, _, _ = serve.window(run, stack, traffic,
                                      config["token_id_limit"], args.seconds,
                                      rate=rate)
            line = serve.summary_line(part)
            line.update(serve.end_to_end(part))
            run.emit(phase="sweep", rate_rps=rate, **line)
    finally:
        stack.stop()
    print(json.dumps({"device": harness.device_record(jax.devices(), 1),
                      "programs_after_setup": run.programs_in_window()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
