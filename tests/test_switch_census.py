"""The census of switches: every environment name, ``FLAGS_*`` spelling and
registered flag the package reads stands in a list here, each with the one
reason it may be set from outside (a deployment setting, or the two callers
that set it differently), and the option counts of the constructors a model
or a cell is built through do not grow.

A name the package reads and the list lacks fails; so does a name the list
has and nothing reads any more.  ROADMAP C5 asks every PR to count options
before and after: this file is the count.
"""
import dataclasses
import inspect
import os
import re

import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu")

#: every ``PADDLE_TPU_*`` / ``FLAGS_*`` name in the package's text
SWITCHES = {
    # -- where things live, how long to wait: deployment settings ------------
    "PADDLE_TPU_AUTOTUNE_CACHE": "deployment: path of the tuner's cache",
    "PADDLE_TPU_CHECKPOINT_DIR": "deployment: where checkpoints go",
    "PADDLE_TPU_FLIGHT_DIR": "deployment: where flight dumps go",
    "PADDLE_TPU_METRICS_FILE": "deployment: where the metrics snapshot goes",
    "PADDLE_TPU_TRACE_FILE": "deployment: where the request trace goes",
    "PADDLE_TPU_STORE_TIMEOUT": "deployment: rendezvous deadline of a job",
    "PADDLE_TPU_RETRY_TRIES": "deployment: retry policy of a flaky network",
    "PADDLE_TPU_RETRY_BASE_DELAY": "deployment: retry policy",
    "PADDLE_TPU_RETRY_MAX_DELAY": "deployment: retry policy",
    "PADDLE_TPU_PREEMPTION_SIGNAL": "deployment: the signal the scheduler "
                                    "of the cluster sends before eviction",
    "PADDLE_TPU_FLIGHT_SIGNAL": "deployment: the operator's post-mortem "
                                "signal",
    "PADDLE_TPU_KV_HOST_BYTES": "deployment: host RAM the KV tier may take",
    "PADDLE_TPU_KV_INDEX_INTERVAL": "deployment: how often a host publishes "
                                    "its prefix index",
    "PADDLE_TPU_TELEMETRY_INTERVAL": "deployment: how often a rank "
                                     "publishes its metrics",
    "PADDLE_TPU_STRAGGLER_PCT": "deployment: the fleet's straggler threshold",
    "PADDLE_TPU_VMEM_LIMIT_MB": "deployment: per-core VMEM of the part the "
                                "static estimator prices for",
    # -- instrumentation, armed by an operator and off in a timed run --------
    "PADDLE_TPU_METRICS": "the benchmark reads the registry, a library "
                          "user may turn it off",
    "PADDLE_TPU_METRICS_GRAD_NORM": "a debugging run reads the norm, a "
                                    "timed run does not pay its sync "
                                    "(it changes the compiled step: "
                                    "ROADMAP C5)",
    "PADDLE_TPU_METRICS_COLLECTIVES": "an operator prices a tp engine's "
                                      "collectives, a server does not pay "
                                      "the extra compile",
    "PADDLE_TPU_METRICS_KV_QUANT_ERROR": "the int8 parity tests read it, "
                                         "a server does not carry the scalar",
    "PADDLE_TPU_TRACING": "an operator traces requests, a timed run does "
                          "not",
    "PADDLE_TPU_TRACE_CAP": "deployment: memory the span buffer may take",
    "PADDLE_TPU_FLIGHT": "a server arms the flight recorder, tests do not",
    "PADDLE_TPU_FLIGHT_RING": "deployment: memory the recorder may take",
    "PADDLE_TPU_HBM": "the OOM post-mortem arms the ledger, a timed run "
                      "does not",
    "PADDLE_TPU_HBM_EVERY": "deployment: how often the armed ledger samples",
    "PADDLE_TPU_LIVENESS": "a launcher arms the watchdog, tests do not",
    "PADDLE_TPU_LIVENESS_DEADLINE": "deployment: stall deadline",
    "PADDLE_TPU_LIVENESS_DEADLINE_": "deployment: per-beacon stall deadline "
                                     "(a prefix: the beacon's name follows)",
    "PADDLE_TPU_LIVENESS_EXIT_RC": "deployment: what the launcher restarts on",
    "PADDLE_TPU_LIVENESS_POLL": "deployment: how often the watchdog looks",
    "PADDLE_TPU_STRICT_COMPILE": "the benchmark and the benches make a "
                                 "recompile an error, a server only warns",
    # -- which code runs: each has two callers that differ -------------------
    "PADDLE_TPU_AUTOTUNE": "an operator lets first calls tune, the "
                           "benchmark resolves defaults (ROADMAP C4)",
    "FLAGS_autotune": "the FLAGS spelling of PADDLE_TPU_AUTOTUNE",
    "PADDLE_TPU_AUTOTUNE_PIN": "an A/B on the chip pins one candidate, "
                               "training pins none (ROADMAP C4)",
    "FLAGS_autotune_pin": "the FLAGS spelling of PADDLE_TPU_AUTOTUNE_PIN",
    "PADDLE_TPU_AUTOTUNE_SAMPLES": "tests time one sample, a warm five",
    "PADDLE_TPU_DISABLE_FLASH": "escape hatch no caller sets (one decision "
                                "with FLAGS_use_flash_attention: debt, "
                                "ROADMAP C5)",
    "PADDLE_TPU_RING_INNER": "escape hatch no caller sets (debt, ROADMAP "
                             "C5)",
    "PADDLE_TPU_MP_OVERLAP": "a tp > 1 server turns the rings on, "
                             "bench_decode --overlap-comm A/Bs them "
                             "against GSPMD's collectives",
    "PADDLE_TPU_SERVE_OVERLAP": "escape hatch no caller sets: the benches "
                                "pass overlap= (debt, ROADMAP C3)",
    "PADDLE_TPU_HANDOFF_HOST": "disjoint prefill and decode meshes stage "
                               "KV through the host, one mesh does not",
    "FLAGS_check_nan_inf": "a debugging session checks every op, a run "
                           "does not",
}

#: every ``define_flag`` of ``utils/flags.py``
FLAGS = {
    "check_nan_inf": "read by core/dispatch.py",
    "autotune": "read by kernels/autotune.py",
    "autotune_samples": "read by kernels/autotune.py",
    "autotune_pin": "read by kernels/autotune.py",
    # registered for set_flags/get_flags parity with the reference and read
    # by nothing: ROADMAP C5 has them as debt
    "use_flash_attention": "no reader (PADDLE_TPU_DISABLE_FLASH decides)",
    "benchmark": "no reader",
    "seed": "no reader",
    "allocator_strategy": "no reader",
    "tpu_matmul_precision": "no reader",
}

_NAME = re.compile(r"PADDLE_TPU_[A-Z0-9_]+|FLAGS_[a-zA-Z0-9_]+")
_DEFINE = re.compile(r"^define_flag\(\s*\"(\w+)\"", re.M)


def _package_text():
    for root, _dirs, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    yield f.read()


def test_every_switch_the_package_reads_is_listed():
    names, flags = set(), set()
    for text in _package_text():
        names.update(_NAME.findall(text))
        flags.update(_DEFINE.findall(text))
    assert names == set(SWITCHES), (
        "unlisted: %s; listed and read by nothing: %s"
        % (sorted(names - set(SWITCHES)), sorted(set(SWITCHES) - names)))
    assert flags == set(FLAGS), (
        "unlisted: %s; listed and not defined: %s"
        % (sorted(flags - set(FLAGS)), sorted(set(FLAGS) - flags)))
    assert all(SWITCHES.values()) and all(FLAGS.values())
    assert len(SWITCHES) <= 43


def _train_step_options():
    from paddle_tpu.jit import TrainStep
    return len(inspect.signature(TrainStep.__init__).parameters) - 1


def _gpt_config_options():
    from paddle_tpu.models.gpt import GPTConfig
    return len(dataclasses.fields(GPTConfig))


def _decode_engine_options():
    from paddle_tpu.serving.engine import DecodeEngine
    return sum(p.default is not inspect.Parameter.empty for p in
               inspect.signature(DecodeEngine.__init__).parameters.values())


def _flag_options():
    with open(os.path.join(PACKAGE, "utils", "flags.py")) as f:
        return len(_DEFINE.findall(f.read()))


@pytest.mark.parametrize("count,most", [
    (_train_step_options, 8),
    (_gpt_config_options, 12),
    (_decode_engine_options, 20),
    (_flag_options, 9),
], ids=["TrainStep", "GPTConfig", "DecodeEngine", "flags"])
def test_option_counts_do_not_grow(count, most):
    """An option more needs two callers that exist and differ
    (simplicity-review, Options); one that only raises this number is a
    choice the code should make itself."""
    assert count() <= most
