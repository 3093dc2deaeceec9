"""Tensor-parallel sharded decode (ISSUE 12): the paged KV pool
partitioned over heads on an ('mp',) mesh, the serving entries jitted
with in/out shardings, host bookkeeping reporting per-chip truth.

Covers the acceptance criteria:
* tp=2 greedy decode on a CPU mesh emits the EXACT token sequence of
  tp=1 and matches its logits within tight tolerance at every position,
  also for the int8+speculative composition;
* compile-exactly-once holds on the sharded engine across slot churn,
  prefix hits and chunked admissions (and across reset() — the bench's
  warmup/timed-drain boundary, where an uncommitted fresh lengths array
  once opened a second jit cache entry);
* the sharded decode HLO is s64-free and partitioned (num_partitions ==
  tp);
* reported per-chip KV accounting (`kv_row_bytes`/`kv_pool_bytes`/
  `kv_bytes_per_token`) is 1/tp of the tp=1 bound;
* `engine_for`'s LRU key accounts for the TP degree (the ISSUE-12
  bugfix): tp=2 after tp=1 builds a fresh sharded engine, while tp=1 —
  spelled or defaulted — maps to one key; `refresh_state()` re-shards a
  changed parameter snapshot onto the engine mesh;
* the trace-audit registry's sharded twins exist and TPU502/TPU503
  (incl. the new SPMD checks) are green on them.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM


def _device_count():
    import jax
    return len(jax.devices())


needs_two = pytest.mark.skipif(
    _device_count() < 2,
    reason="tensor-parallel tests need >= 2 devices (conftest sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _tiny_model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    from paddle_tpu.serving.engine import DecodeEngine
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 16)
    return DecodeEngine(model, **kw)


def _greedy_drive(eng, prompts, steps=6):
    """Prefill + greedy decode; returns (token seqs, per-step logits)."""
    seqs, logits = [], []
    for i, p in enumerate(prompts):
        tok, lg = eng.prefill(i, p, temperature=0.0)
        seqs.append([tok])
        logits.append([np.asarray(lg)])
    n = len(prompts)
    for _ in range(steps):
        toks = [s[-1] for s in seqs]
        nt, lg = eng.decode(toks, [True] * n, [0.0] * n, [0] * n,
                            [1.0] * n)
        for b in range(n):
            seqs[b].append(int(nt[b]))
            logits[b].append(np.asarray(lg[b]))
    return seqs, logits


# ---------------------------------------------------------------------------
# parity: tp=2 == tp=1, both layer layouts, int8+spec composition
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@needs_two
def test_tp2_greedy_parity_every_position():
    """THE acceptance criterion: the head-sharded engine's greedy tokens
    match tp=1 exactly and its logits match within tight tolerance at
    every position (GSPMD reduction-order drift only)."""
    m = _tiny_model()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 512, (5,)), rng.integers(0, 512, (19,))]
    out = {}
    for tp in (1, 2):
        eng = _engine(m, seed=3, tp=tp)
        out[tp] = _greedy_drive(eng, prompts)
        assert eng.decode_compile_count == 1
    assert out[1][0] == out[2][0], \
        "tp=2 greedy tokens diverged from tp=1"
    for b in range(len(prompts)):
        for l1, l2 in zip(out[1][1][b], out[2][1][b]):
            np.testing.assert_allclose(l2, l1, rtol=2e-4, atol=2e-4)


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@needs_two
def test_tp2_int8_spec_composed_matches_tp1():
    """All three multiplicative levers composed: tp=2 over the int8 pool
    with speculative verify emits the same greedy completions as the
    same engine at tp=1 (spec greedy is bit-identical to non-spec by
    construction, so this transitively matches plain decode too)."""
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 512, (n,)) for n in (7, 13, 9)]
    results = {}
    for tp in (1, 2):
        eng = _engine(m, num_slots=2, max_len=64, page_size=16, tp=tp,
                      spec_k=3, kv_dtype="int8", seed=0)
        sched = ContinuousBatchingScheduler(eng)
        rids = [sched.submit(Request(prompt=p, max_new_tokens=8,
                                     temperature=0.0))
                for p in prompts]
        res = sched.run()
        results[tp] = [res[r].tokens.tolist() for r in rids]
        assert eng.verify_compile_count == 1
    assert results[1] == results[2], \
        "tp=2 int8+spec completions diverged from tp=1"


# ---------------------------------------------------------------------------
# compile-once + HLO discipline on the sharded entries
# ---------------------------------------------------------------------------

@needs_two
def test_tp2_compile_once_across_churn_and_reset():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8,
                  prefill_chunk=8, tp=2)
    rng = np.random.default_rng(53)
    shared = rng.integers(0, 512, (16,))

    def drive():
        sched = ContinuousBatchingScheduler(eng)
        for i in range(5):
            prompt = shared if i % 2 else rng.integers(0, 512,
                                                       (5 + 7 * i,))
            sched.submit(Request(prompt=prompt, max_new_tokens=5,
                                 temperature=0.0))
        sched.run()

    drive()
    eng.reset()   # the bench's warmup boundary: must NOT reopen a cache
    drive()
    assert eng.decode_compile_count == 1, \
        "sharded decode retraced: %d programs" % eng.decode_compile_count
    assert eng.prefill_compile_count == 1
    assert int(eng._cow._cache_size()) <= 1


@needs_two
def test_tp2_decode_hlo_s64_free_and_partitioned():
    import re

    import jax
    from paddle_tpu.analysis import S64_COMPUTE_OPS
    from paddle_tpu.core.dtype import x64_scope
    from paddle_tpu.distributed import mesh as _mesh
    m = _tiny_model()
    eng = _engine(m, tp=2)
    ins, outs = eng._entry_shardings["serving.decode"]
    with x64_scope(False), _mesh.mesh_scope(eng.mesh):
        lowered = jax.jit(
            eng._decode_fn,
            donate_argnums=eng._decode_donate_argnums,
            in_shardings=ins, out_shardings=outs).lower(
            *eng.decode_trace_args())
    txt = lowered.as_text()
    mm = re.search(r"mhlo\.num_partitions\s*=\s*(\d+)", txt)
    assert mm and int(mm.group(1)) == 2, \
        "sharded decode did not lower as a 2-partition program"
    hlo = lowered.compile().as_text()
    assert "f64[" not in hlo
    for op in S64_COMPUTE_OPS:
        pat = re.compile(r"s64\[[0-9,]*\]\S* " + op + r"\(")
        assert not pat.search(hlo), \
            "s64 %s leaked into the sharded decode" % op
    # the partitioned program must actually move data over the mesh
    assert re.search(r"\b(all-reduce|all-gather|reduce-scatter|"
                     r"collective-permute|all-to-all)\b", hlo), \
        "no collectives in the partitioned decode — sharding inert"


# ---------------------------------------------------------------------------
# per-chip accounting
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@needs_two
def test_kv_accounting_reports_per_chip_truth():
    m = _tiny_model()
    vals = {}
    for tp in (1, 2):
        eng = _engine(m, tp=tp)
        eng.prefill(0, np.arange(5, dtype=np.int32), temperature=0.0)
        eng.prefill(1, np.arange(9, dtype=np.int32), temperature=0.0)
        for _ in range(3):
            eng.decode([1, 2], [True, True], [0.0, 0.0], [0, 0],
                       [1.0, 1.0])
        vals[tp] = (eng.kv_row_bytes(), eng.kv_pool_bytes(),
                    eng.kv_bytes_per_token())
    assert vals[1][0] == 2 * vals[2][0]
    assert vals[1][1] == 2 * vals[2][1]
    # the acceptance ratio: per-chip decode bytes/token ~ 1/tp
    assert vals[2][2]["paged"] == pytest.approx(
        vals[1][2]["paged"] / 2, rel=1e-6)
    assert vals[2][2]["flat"] == pytest.approx(
        vals[1][2]["flat"] / 2, rel=1e-6)


@needs_two
def test_tp2_pool_is_sharded_on_device():
    """The pool actually LIVES split: each of the two devices holds half
    the head axis (HBM per chip is the point, not just accounting)."""
    m = _tiny_model()
    eng = _engine(m, tp=2)
    shards = eng.cache.k.sharding.shard_shape(eng.cache.k.shape)
    assert shards[3] == eng.cache.k.shape[3] // 2, \
        "pool heads axis not split across the mesh: %r" % (shards,)
    assert len(eng.cache.k.devices()) == 2


# ---------------------------------------------------------------------------
# engine_for key + refresh_state (the ISSUE-12 bugfix)
# ---------------------------------------------------------------------------

@needs_two
def test_engine_for_tp_is_part_of_the_geometry_key():
    from paddle_tpu.serving import engine_for
    m = _tiny_model()
    e_default = engine_for(m, num_slots=2, max_len=32, page_size=16)
    e_tp1 = engine_for(m, num_slots=2, max_len=32, page_size=16, tp=1)
    # tp=1 spelled or defaulted is ONE geometry: a kwargs-carried tp
    # would have split these into two engines pinning two full KV pools
    assert e_tp1 is e_default
    e_tp2 = engine_for(m, num_slots=2, max_len=32, page_size=16, tp=2)
    # the regression: a tp=2 request must NOT reuse the unsharded cache
    # geometry (single-chip buffers fed to a sharded program)
    assert e_tp2 is not e_default
    assert e_tp2.tp == 2 and e_tp2.mesh is not None
    # and both stay cached under their own keys
    assert engine_for(m, num_slots=2, max_len=32, page_size=16) \
        is e_default
    assert engine_for(m, num_slots=2, max_len=32, page_size=16, tp=2) \
        is e_tp2


@needs_two
def test_refresh_state_reshards_changed_params_onto_the_mesh():
    import jax
    m = _tiny_model()
    eng = _engine(m, tp=2)
    prompt = np.arange(7, dtype=np.int32)
    eng.prefill(0, prompt, temperature=0.0)
    eng.decode([1, 0], [True, False], [0.0, 0.0], [0, 0], [1.0, 1.0])
    # perturb a parameter (a training step between generate rounds)
    w = m.gpt.wte.weight
    w.set_value(paddle.to_tensor(np.asarray(w.numpy()) + 1e-3))
    eng.reset()
    eng.refresh_state()
    # every leaf sits on the engine mesh again (a raw functional_state
    # snapshot after training would raise a device mismatch at dispatch)
    for name, leaf in eng.state.items():
        assert set(leaf.devices()) <= set(eng.mesh.devices.flat), name
    tok, _ = eng.prefill(0, prompt, temperature=0.0)
    eng.decode([tok, 0], [True, False], [0.0, 0.0], [0, 0], [1.0, 1.0])
    assert eng.decode_compile_count == 1   # same avals/shardings: no retrace


@needs_two
def test_refresh_state_unchanged_keeps_prefix_cache_and_placement():
    """The review-found regression: tp engines hold device_put COPIES
    in .state, so an identity test against them read every unchanged
    re-snapshot (every engine_for reuse) as a change — silently
    dropping the prefix cache and re-uploading the whole tree per
    generate() round.  The change test runs against the UNSHARDED
    source leaves."""
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8, tp=2)
    prompt = np.arange(20, dtype=np.int32)
    eng.prefill(0, prompt, temperature=0.0)     # registers the prefix
    eng.free_slot(0)                            # pages -> free-but-cached
    assert eng._alloc.lookup_prefix(prompt)[1] > 0
    placed = dict(eng.state)
    eng.refresh_state()                         # the engine_for reuse path
    # unchanged params: cache kept, no re-shard (same placed leaves)
    assert eng._alloc.lookup_prefix(prompt)[1] > 0, \
        "unchanged refresh_state dropped the prefix cache on a tp engine"
    assert all(eng.state[k] is placed[k] for k in placed), \
        "unchanged refresh_state re-uploaded the parameter tree"


@needs_two
def test_tp1_engine_is_single_chip_under_a_stale_training_mesh():
    """The review-found leak: the cache walk's head constraints resolve
    the GLOBAL mesh, so a tp=1 engine traced in a process that still
    has a training mesh declaring 'mp' installed would silently become
    an SPMD program over the training devices.  tp=1 engines install
    mesh None around their traced calls (mesh_scope(None)), keeping
    'tp=1 is byte-identical to the unsharded engine' true in mesh-laden
    processes."""
    import re

    import jax
    from paddle_tpu.core.dtype import x64_scope
    from paddle_tpu.distributed import mesh as _mesh
    m = _tiny_model()
    prompts = [np.arange(5, dtype=np.int32)]
    eng_clean = _engine(m, num_slots=1, seed=3)
    ref, _ = _greedy_drive(eng_clean, prompts, steps=4)
    prev = _mesh.get_mesh()
    _mesh.init_mesh({"mp": 2})                  # leftover training mesh
    try:
        eng = _engine(m, num_slots=1, seed=3)
        got, _ = _greedy_drive(eng, prompts, steps=4)
        assert got == ref
        assert eng.decode_compile_count == 1
        with x64_scope(False), _mesh.mesh_scope(eng.mesh):
            txt = jax.jit(
                eng._decode_fn,
                donate_argnums=eng._decode_donate_argnums).lower(
                *eng.decode_trace_args()).as_text()
        mm = re.search(r"mhlo\.num_partitions\s*=\s*(\d+)", txt)
        assert mm is None or int(mm.group(1)) == 1, \
            "tp=1 decode lowered multi-partition under a stale mesh"
    finally:
        _mesh.set_mesh(prev)


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_tp_validation_errors():
    from paddle_tpu.serving.engine import DecodeEngine
    m = _tiny_model()
    with pytest.raises(ValueError, match="paged"):
        DecodeEngine(m, num_slots=2, max_len=64, paged=False, tp=2)
    with pytest.raises(ValueError, match="tp must be >= 1"):
        _engine(m, tp=0)
    if _device_count() >= 3:
        with pytest.raises(ValueError, match="divide"):
            _engine(m, tp=3)   # tiny has 4 heads; 3 does not divide
    with pytest.raises(ValueError, match="devices"):
        _engine(m, tp=1024)


# ---------------------------------------------------------------------------
# trace-audit registration (TPU502 donations + TPU503 SPMD checks)
# ---------------------------------------------------------------------------

@needs_two
@pytest.mark.slow
def test_tp_audit_programs_registered_and_green():
    from paddle_tpu.analysis.trace.collective_order import \
        CollectiveOrderPass
    from paddle_tpu.analysis.trace.core import TraceAnalyzer
    from paddle_tpu.analysis.trace.donation import DonationPass
    from paddle_tpu.analysis.trace.programs import build_programs
    programs, skipped, errors = build_programs(["serving/*_tp"])
    assert not errors, errors
    names = {p.name for p in programs}
    assert {"serving/decode_step_tp", "serving/prefill_chunk_tp",
            "serving/spec_verify_tp"} <= names, names
    report = TraceAnalyzer(
        root="/root/repo",
        passes=[DonationPass, CollectiveOrderPass]).run(programs)
    assert not report.findings, [str(f) for f in report.findings]
    assert not report.errors, report.errors
    for p in programs:
        assert p.meta.get("spmd_sharded") is True
        assert p.meta["mesh_axes"] == {"mp": 2}


@needs_two
def test_tpu503_spmd_checks_catch_mismatch_and_inert_sharding():
    """Negative coverage for the new TPU503 checks: a declared-sharded
    program whose lowering is single-partition (the shardings silently
    never applied) and one whose declared mesh disagrees with the
    lowered partition count must both be findings."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis.trace.collective_order import \
        CollectiveOrderPass
    from paddle_tpu.analysis.trace.core import TraceProgram

    def f(x):
        return x * 2.0

    jitted = jax.jit(f)
    x = jnp.ones((8, 8), jnp.float32)
    lowered = jitted.lower(x)
    prog = TraceProgram(
        name="fixture/unsharded_claims_sharded",
        jaxpr=jax.make_jaxpr(jitted)(x),
        lowered_text=lowered.as_text(), lowered=lowered,
        meta={"mesh_axes": {"mp": 2}, "spmd_sharded": True})
    findings = list(CollectiveOrderPass().check(prog))
    assert findings, "single-partition lowering of a declared-sharded " \
                     "program produced no TPU503 finding"
    assert any("num_partitions" in f.message for f in findings)

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_tp2_overlapped_loop_parity_and_compile_once(monkeypatch):
    """ISSUE 13 x ISSUE 12: the overlapped loop's device-token threading
    on a SHARDED engine — the threaded (committed, mesh-replicated)
    outputs and the committed host-token first dispatch must hit the
    same sharded program (strict watchdog), and greedy output must
    match the sync loop bit-for-bit."""
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    model = _tiny_model()
    cfg = model.config

    def drive(overlap):
        eng = _engine(model, tp=2, page_size=8)
        sched = ContinuousBatchingScheduler(eng, overlap=overlap)
        rng = np.random.default_rng(1)
        rids = [sched.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, (8,)),
            max_new_tokens=6, temperature=0.0)) for _ in range(4)]
        res = sched.run()
        assert eng.decode_compile_count == 1
        return [tuple(int(t) for t in res[r].tokens) for r in rids]

    assert drive(False) == drive(True)
