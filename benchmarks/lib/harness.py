"""What every kind of run shares: phase lines, files found by name, the
model built through the program's own entry points and given the seed's
weights, the profiler window, the device record."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark_spec(path=None):
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def find_workload(spec, name):
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit("benchmark: no workload %r in BENCHMARK.json" % name)


def config_of(spec, name):
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(ROOT, c["file"])
    raise SystemExit("benchmark: no configuration %r in BENCHMARK.json"
                     % name)


def traffic_of(cell):
    """The cell's traffic file, found by the traffic mix's name.  (A
    rehearsal spec outside BENCHMARK.json, as the tests write, may point at
    a file elsewhere with ``traffic_file``.)"""
    if "traffic_file" in cell:
        return load_json(ROOT, cell["traffic_file"])
    return load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")


def load_cell(spec, workload, rehearse):
    """(cell, configuration, traffic) of ``workload``; a rehearsal takes
    the tiny sizes the two data files give under ``rehearse``."""
    cell = find_workload(spec, workload)
    config = config_of(spec, cell["config"])
    traffic = traffic_of(cell)
    if rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
        config = dict(config,
                      token_id_limit=config["rehearse_token_id_limit"],
                      gpt_config={**config["gpt_config"],
                                  **config["rehearse_gpt_config"]})
    return cell, config, traffic


def metric_names(spec, section, workload):
    """Names of the metrics of ``section`` that ``workload`` reports."""
    return [m["name"] for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def layer_reader(name):
    """``benchmarks/layer_metrics/<name>.py``'s ``read`` function."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Counts, from JAX's own monitoring events, every program the process
    asked the compiler for: found in the persistent cache (``hits``) or
    compiled (``misses``).  Wider than the program's watchdog, which sees
    only its compile-once entries: eager ops compile small programs too."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.compile_seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += seconds

    def snapshot(self):
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "compile_seconds": round(self.compile_seconds, 3)}

    @staticmethod
    def delta(before, after):
        return {k: round(after[k] - before[k], 3) for k in after}


class Run:
    """One run's clock, phase lines and record.  ``t_process`` is the time
    the process started measuring itself (top of ``run.py``)."""

    def __init__(self, args, t_process, rehearse):
        self.args = args
        self.t_process = t_process
        self.rehearse = rehearse
        self.setup_parts = {}
        self.record = {}
        self.compile_log = CompileLog()
        self._t_part = t_process

    def emit(self, **fields):
        if self.rehearse:
            fields = {"rehearsal": True, **fields}
        print(json.dumps(fields), flush=True)

    def part(self, name):
        """Close the set-up part that ran since the last call."""
        now = time.perf_counter()
        self.setup_parts[name] = now - self._t_part
        self._t_part = now
        self.emit(phase="setup", part=name,
                  seconds=round(self.setup_parts[name], 3))

    def setup_done(self):
        """The first timed event is next: everything until now is set-up."""
        self.record["setup_s"] = time.perf_counter() - self.t_process
        self.emit(phase="setup", part="total",
                  seconds=round(self.record["setup_s"], 3),
                  parts={k: round(v, 3) for k, v in self.setup_parts.items()},
                  programs=self.compile_log.snapshot())
        self.record["programs_at_setup"] = self.compile_log.snapshot()

    def programs_in_window(self):
        """Programs compiled or loaded since ``setup_done``."""
        return CompileLog.delta(self.record["programs_at_setup"],
                                self.compile_log.snapshot())


class PartClock:
    """Seconds of each named part since the last one, for a phase line."""

    def __init__(self):
        self.parts, self._t = {}, time.perf_counter()

    def part(self, name):
        now = time.perf_counter()
        self.parts[name] = round(now - self._t, 3)
        self._t = now


def build_model(run, config, amp):
    """The model through the program's constructor, then every leaf
    overwritten with the seed's weights.  Returns (model, cfg, weights):
    ``weights`` is the dict that went in (names, served dtypes)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from benchmarks.lib import seeds, weights as weights_mod
    cfg = GPTConfig(**config["gpt_config"])
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    paddle.seed(seeds.small_seed(run.args.seed))
    model = GPTForCausalLM(cfg)
    if amp:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    run.part("model_build")
    made = weights_mod.make_weights(
        seeds.key_words(run.args.seed, "weights"), model.functional_state(),
        cfg.initializer_range, cfg.num_hidden_layers)
    model.load_functional_state(made)
    run.part("weights_from_seed")
    return model, cfg, made


class Profiler:
    """A profiler window whose files live under TMPDIR and are removed once
    reduced.  The Python tracer is off: it slows the host it measures."""

    def __init__(self):
        self.dir = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop_and_reduce(self):
        import jax
        from benchmarks.lib import trace
        jax.profiler.stop_trace()
        try:
            return trace.load(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_record(devices, chips):
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}
