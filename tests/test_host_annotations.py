"""Host spans on the device's clock (``observability.tracing.annotation``):
``pt.<layer>.<phase>`` ``TraceAnnotation`` spans land in the profiler's own
trace (the xplane's host plane), beside the device's operations, where the
request tracer's ``perf_counter_ns`` spans never could; with no session a
site costs a flag test."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import tracing


def _host_events(trace_dir):
    """{event name: count} over the host planes of the session's xplane."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert found, "the session wrote no xplane"
    names = {}
    for plane in ProfileData.from_file(found[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                names[event.name] = names.get(event.name, 0) + 1
    return names


def _session(tmp_path, body):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(tmp_path))


def test_annotation_names_and_no_session_is_harmless():
    with tracing.annotation("train", "step_call") as a:
        assert a is not None
    assert "annotation" in tracing.__all__


def test_train_step_call_lands_in_the_host_plane(tmp_path):
    net = nn.Sequential(nn.Linear(4, 4))
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(),
                     paddle.optimizer.SGD(parameters=net.parameters(),
                                          learning_rate=0.1))
    x = jnp.ones((2, 4), jnp.float32)
    step(x, x).numpy()                  # compile outside the session

    def body():
        for _ in range(3):
            step(x, x).numpy()
    names = _session(tmp_path, body)
    assert names.get("pt.train.step_call") == 3


@pytest.fixture(scope="module")
def serving_host_events(tmp_path_factory):
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    model.eval()
    engine = DecodeEngine(model, num_slots=2, max_len=64, page_size=16)
    rng = np.random.default_rng(0)

    def drive():
        sched = ContinuousBatchingScheduler(engine)
        for _ in range(2):
            sched.submit(Request(prompt=rng.integers(0, 500, (8,)),
                                 max_new_tokens=4, temperature=0.0))
        return sched.run()
    drive()                             # compile outside the session
    return _session(tmp_path_factory.mktemp("serve"), drive)


@pytest.mark.parametrize("name", [
    "pt.sched.admit", "pt.sched.prefill_dispatch", "pt.sched.decode_dispatch",
    "pt.sched.fetch", "pt.sched.deliver", "pt.engine.prefill_chunk",
    "pt.engine.decode"])
def test_serving_loop_spans_land_in_the_host_plane(serving_host_events,
                                                   name):
    assert serving_host_events.get(name, 0) >= 1, sorted(
        n for n in serving_host_events if n.startswith("pt."))


def test_the_engine_dispatch_span_is_both(tmp_path):
    """With the request tracer on, the same context writes the engine-lane
    span with its compile-count attrs; with it off, NOOP_SPAN by identity."""
    from paddle_tpu.serving.engine import DecodeEngine
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    model.eval()
    tracer = tracing.Tracer()
    engine = DecodeEngine(model, num_slots=2, max_len=64, page_size=16,
                          tracer=tracer)
    engine.prefill(0, np.arange(8) % 500, temperature=0.0)
    spans = [s for s in tracer.spans() if s["name"] == "engine.prefill_chunk"]
    assert spans and spans[0]["attrs"] == {"compile_count": 1, "compiles": 1}
    assert spans[0]["end_ns"] > spans[0]["start_ns"]
    engine.prefill(1, np.arange(8) % 500, temperature=0.0)
    again = [s for s in tracer.spans()
             if s["name"] == "engine.prefill_chunk"][-1]
    assert again["attrs"] == {"compile_count": 1, "compiles": 0}
    off = DecodeEngine(model, num_slots=2, max_len=64, page_size=16,
                       tracer=tracing.NOOP_TRACER)
    with off._dispatch_span("decode", off._decode) as ctx:
        assert ctx._span is tracing.NOOP_SPAN
