"""What every kind of run shares: phase lines, files found by name, the
model built through the program's own entry points and given the seed's
weights, the profiler window, the device record."""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
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


def config_entry(spec, name):
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit("benchmark: no configuration %r in BENCHMARK.json"
                     % name)


def traffic_of(cell):
    """The cell's traffic file, found by the traffic mix's name.  (A
    rehearsal spec outside BENCHMARK.json, as the tests write, may point at
    a file elsewhere with ``traffic_file``.)"""
    if "traffic_file" in cell:
        return load_json(ROOT, cell["traffic_file"])
    return load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")


def load_family(name, path=None):
    """The model family ``name``: ``benchmarks/families/<name>.py``, or the
    file at ``path`` from the root of the repo (a rehearsal spec's
    configuration entry may carry ``family_file``, as a cell may carry
    ``traffic_file``).  ``benchmarks/README.md`` fixes what it provides."""
    if path is None:
        module = "benchmarks.families." + name
        try:
            return importlib.import_module(module)
        except ModuleNotFoundError as e:
            if e.name != module:
                raise
            raise SystemExit("benchmark: no model family %r under "
                             "benchmarks/families" % name)
    return _module_at("family_file_" + name, os.path.join(ROOT, path))


def _module_at(name, path):
    """The module in the file at ``path``, loaded once."""
    mod = sys.modules.get(name)
    if mod is None or not os.path.samefile(mod.__file__, path):
        mod_spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[name] = mod
        mod_spec.loader.exec_module(mod)
    return mod


def as_run(config, rehearse, family_file=None):
    """A configuration file's dict as a run takes it: ``family`` is the
    loaded family (the file's ``family`` key; absent means ``gpt``),
    ``model`` the model dict under the family's own key, with the tiny
    sizes the file gives for a rehearsal laid over it."""
    family = load_family(config.get("family", "gpt"), family_file)
    model = dict(config[family.MODEL_KEY])
    limit = config["token_id_limit"]
    if rehearse:
        model.update(config[family.REHEARSE_KEY])
        limit = config["rehearse_token_id_limit"]
    return dict(config, family=family, model=model, token_id_limit=limit)


def load_cell(spec, workload, rehearse):
    """(cell, configuration, traffic) of ``workload``; a rehearsal takes
    the tiny sizes the two data files give under ``rehearse``."""
    cell = find_workload(spec, workload)
    entry = config_entry(spec, cell["config"])
    config = as_run(load_json(ROOT, entry["file"]), rehearse,
                    entry.get("family_file"))
    traffic = traffic_of(cell)
    if rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
    return cell, config, traffic


def metric_names(spec, section, workload):
    """Names of the metrics of ``section`` that ``workload`` reports."""
    return [m["name"] for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def layer_reader(name):
    """``benchmarks/layer_metrics/<name>.py``'s ``read`` function."""
    return _module_at(
        "benchmarks.layer_metrics." + name.replace(".", "_"),
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read


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
    """Seconds of each named part since the last one, for a phase line.
    With ``memory`` also, for each part, the bytes of live device arrays
    before it and the device's ``peak_bytes_in_use`` after it (a lifetime
    peak: the part that raised it is the first that shows the new value)."""

    def __init__(self, memory=False):
        self.parts, self._t = {}, time.perf_counter()
        self.memory = {} if memory else None
        self._live = live_bytes() if memory else 0

    def part(self, name):
        now = time.perf_counter()
        self.parts[name] = round(now - self._t, 3)
        if self.memory is not None:
            self.memory[name] = {"live_before": self._live,
                                 "peak_after": peak_bytes()}
            self._live = live_bytes()
            now = time.perf_counter()
        self._t = now


def build_model(run, config, amp):
    """The model through the program's constructor, as the configuration's
    family calls it, then every leaf overwritten with the seed's weights.
    Returns (model, weights): ``weights`` is the dict that went in (names,
    served dtypes)."""
    import paddle_tpu as paddle
    from benchmarks.lib import seeds, weights as weights_mod
    paddle.seed(seeds.small_seed(run.args.seed))
    model = config["family"].build_model(config["model"])
    if amp:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    run.part("model_build")
    made = weights_mod.make_weights(
        seeds.key_words(run.args.seed, "weights"), model.functional_state(),
        config["family"], config["model"])
    model.load_functional_state(made)
    run.part("weights_from_seed")
    return model, made


@contextlib.contextmanager
def no_persistent_cache():
    """Programs compiled inside are neither looked up in JAX's persistent
    compilation cache nor written to it.  For the check's whole-model
    programs: the machine with the chip caps the cache's size
    (``JAX_COMPILATION_CACHE_MAX_SIZE``, 192 MiB in PR 27's runs), they are
    tens of MB each, and least-recently-used eviction then throws out the
    programs of the set-up, which the next run compiles again inside
    ``setup_s``.  The check runs after the window and outside ``setup_s``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


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


def live_bytes():
    """Bytes of the device arrays this process holds right now (an array
    sharded over a mesh counts whole)."""
    import jax
    return int(sum(a.nbytes for a in jax.live_arrays()))


def peak_bytes(devices=None):
    """``peak_bytes_in_use`` of the fullest of ``devices`` (default: every
    local one); 0 where the backend keeps no such count, as the CPU's."""
    import jax
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices or jax.local_devices()))


def program_bytes(compiled):
    """A compiled program's own memory analysis, in bytes: what it takes
    as arguments, hands back, needs for temporaries and for its code, and
    how much of its output lives in its (donated) arguments.  ``total`` is
    what the device holds while it runs: arguments + outputs - aliased +
    temporaries + code.  (The arithmetic of
    ``paddle_tpu/observability/costs.py::memory_analysis_dict``, kept here
    with the yardstick.)"""
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k + "_size_in_bytes"))
           for k in ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = (out["argument"] + out["output"] - out["alias"]
                    + out["temp"] + out["generated_code"])
    return out


def device_record(devices, chips):
    used = devices[:chips]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes(used)}
