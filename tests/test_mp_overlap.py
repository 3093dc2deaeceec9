"""Decomposed collective matmuls (ISSUE 20): the ppermute rings behind
``paddle_tpu.distributed.mp_overlap`` and their consumers.

Covers:
* ring correctness against dense references for every island kind (row
  RS+AG ring, column local-fwd, rotate-weights LM head, masked-gather
  vocab embed, the 3-ppermute fused-qkv re-deal), including chunked
  rings;
* the custom_vjp backwards match dense autodiff (the train-path
  contract behind the Megatron layers);
* the three-level switch: off ⇒ the wrappers return ``None`` and
  callers keep today's GSPMD lowering; non-viable shapes fall back the
  same way;
* tp=2 serving: the overlapped engine's greedy stream is BIT-IDENTICAL
  to the monolithic engine (n=2 two-term f32 sums commute), compiles
  once, and its partitioned decode HLO has ZERO monolithic all-gathers
  / all-to-alls with the ppermute chain present (structural check via
  ``costs.collective_stats``'s launches-vs-bytes split);
* mp=4 training: overlapped GPT train grads match the GSPMD baseline
  to tight tolerance, loss bitwise-equal trace-to-trace;
* `engine_for` folds the resolved overlap switch into its LRU key
  (env-on + tp=2 and explicit ``overlap_comm=True`` share one engine);
* the ``mp_overlap`` autotune family resolves, and the
  ``mp.overlap_chunks`` counter is driven at trace time.
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
    reason="overlap tests need >= 2 devices (conftest sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs_four = pytest.mark.skipif(
    _device_count() < 4, reason="needs >= 4 devices")


def _mp_mesh(n):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]), ("mp",))


def _scoped(n, chunks=None):
    import contextlib

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed import mp_overlap as mpo

    @contextlib.contextmanager
    def ctx():
        with mesh_mod.mesh_scope(_mp_mesh(n)), \
                mpo.overlap_scope(True, chunks):
            yield
    return ctx()


def _jit(fn):
    """The islands as production runs them — traced under jit.  Run
    eagerly, jax.shard_map executes its body op by op on every device
    (tens of seconds for these toy shapes).  A fresh wrapper per call: the
    islands read the mesh and chunk scopes at trace time, which no jit
    cache key records."""
    import jax
    return jax.jit(lambda *a: fn(*a))


def _tiny_model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


# ---------------------------------------------------------------------------
# ring islands vs dense references
# ---------------------------------------------------------------------------

@needs_four
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("chunks", [1, 2])
def test_row_ring_matches_dense(n, chunks):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mp_overlap as mpo

    x = jax.random.normal(jax.random.key(0), (3, 4, 16), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (16, 8), jnp.float32)
    b = jax.random.normal(jax.random.key(2), (8,), jnp.float32)
    with _scoped(n, chunks):
        out = _jit(mpo.row_parallel_matmul)(x, w, b)
    assert out is not None
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w + b),
                               rtol=1e-5, atol=1e-5)


@needs_four
@pytest.mark.parametrize("n", [2, 4])
def test_col_lm_embed_match_dense(n):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mp_overlap as mpo

    x = jax.random.normal(jax.random.key(3), (2, 5, 12), jnp.float32)
    w = jax.random.normal(jax.random.key(4), (12, 16), jnp.float32)
    wte = jax.random.normal(jax.random.key(5), (32, 12), jnp.float32)
    ids = jnp.asarray([[0, 7, 31, 15], [3, 3, 30, 1]], jnp.int32)
    with _scoped(n):
        col = _jit(mpo.column_parallel_matmul)(x, w)
        lm = _jit(mpo.lm_head_matmul)(x, wte)
        emb = _jit(mpo.vocab_embed)(ids, wte)
    np.testing.assert_allclose(np.asarray(col), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lm), np.asarray(x @ wte.T),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(emb),
                               np.asarray(jnp.take(wte, ids, axis=0)),
                               rtol=1e-6, atol=1e-6)


@needs_four
@pytest.mark.parametrize("n", [2, 4])
def test_qkv_redeal_exact(n):
    """The 3-ppermute re-deal is a pure data movement — exact equality
    against the slice-then-reshape reference (gcd(3, n) == 1).  Operands
    are small integers, so every product and partial sum is exact in f32
    and the comparison sees the data movement alone: with random reals
    the backend's matmul rounds a 12-column shard and the 48-column whole
    differently in the last place (seen on XLA:CPU at n=4)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mp_overlap as mpo

    nh, hd = 4, 4
    h = nh * hd
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randint(-4, 5, (2, 3, 8)), jnp.float32)
    w = jnp.asarray(rng.randint(-4, 5, (8, 3 * h)), jnp.float32)
    b = jnp.asarray(rng.randint(-4, 5, (3 * h,)), jnp.float32)
    ref = np.asarray(x @ w + b)
    refs = [ref[..., i * h:(i + 1) * h].reshape(2, 3, nh, hd)
            for i in range(3)]
    with _scoped(n):
        out = _jit(lambda x_, w_, b_: mpo.qkv_heads(x_, w_, b_, nh, hd))(
            x, w, b)
    assert out is not None
    for got, want in zip(out, refs):
        assert np.array_equal(np.asarray(got), want)
    # bias-free variant shares the body
    refs0 = [np.asarray(x @ w)[..., i * h:(i + 1) * h].reshape(2, 3, nh,
                                                               hd)
             for i in range(3)]
    with _scoped(n):
        out0 = _jit(lambda x_, w_: mpo.qkv_heads(x_, w_, None, nh, hd))(
            x, w)
    for got, want in zip(out0, refs0):
        assert np.array_equal(np.asarray(got), want)


@needs_four
@pytest.mark.parametrize("n", [2, 4])
def test_custom_vjp_grads_match_dense(n):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mp_overlap as mpo

    x = jax.random.normal(jax.random.key(9), (4, 16), jnp.float32)
    w = jax.random.normal(jax.random.key(10), (16, 8), jnp.float32)
    wte = jax.random.normal(jax.random.key(11), (32, 16), jnp.float32)

    def cot(f, *args):
        return _jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                             argnums=tuple(range(len(args)))))(*args)

    dx_ref, dw_ref = cot(lambda a, b: a @ b, x, w)
    dl_ref, dt_ref = cot(lambda a, b: a @ b.T, x, wte)
    with _scoped(n):
        dx, dw = cot(lambda a, b: mpo.row_parallel_matmul(a, b), x, w)
        cx, cw = cot(lambda a, b: mpo.column_parallel_matmul(a, b), x, w)
        lx, lt = cot(lambda a, b: mpo.lm_head_matmul(a, b), x, wte)
    for got, want in ((dx, dx_ref), (dw, dw_ref), (cx, dx_ref),
                      (cw, dw_ref), (lx, dl_ref), (lt, dt_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the switch: off ⇒ None, non-viable ⇒ None
# ---------------------------------------------------------------------------

def test_off_and_nonviable_return_none(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.distributed import mp_overlap as mpo

    monkeypatch.delenv(mpo.ENV_FLAG, raising=False)
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    # switch off: no island regardless of mesh
    assert mpo.row_parallel_matmul(x, w) is None
    assert not mpo.row_viable(8)
    if _device_count() >= 2:
        # switch on but no mp mesh installed ⇒ no island
        with mpo.overlap_scope(True):
            assert mpo.active() is None
        with _scoped(2):
            # per-call arg wins over the enabling scope
            assert mpo.row_parallel_matmul(x, w, arg=False) is None
            # non-divisible contraction dim falls back
            assert mpo.row_parallel_matmul(
                jnp.ones((2, 7), jnp.float32),
                jnp.ones((7, 4), jnp.float32)) is None
            assert mpo.qkv_viable(6, 4)          # gcd(3, 2) == 1
    if _device_count() >= 3:
        with _scoped(3):
            # tp % 3 == 0 breaks the 3-ppermute bijection: not viable
            assert not mpo.qkv_viable(6, 4)
            assert mpo.qkv_heads(x.reshape(2, 1, 8),
                                 jnp.ones((8, 72), jnp.float32), None,
                                 6, 4) is None
    # env spelling
    monkeypatch.setenv(mpo.ENV_FLAG, "1")
    assert mpo.env_enabled() and mpo.enabled()
    monkeypatch.setenv(mpo.ENV_FLAG, "0")
    assert not mpo.enabled()


def test_overlap_scope_nesting_and_chunks_pin():
    from paddle_tpu.distributed import mp_overlap as mpo

    assert mpo.scope_chunks() is None
    with mpo.overlap_scope(True, 2):
        assert mpo.enabled() and mpo.scope_chunks() == 2
        with mpo.overlap_scope(False):
            assert not mpo.enabled()
        assert mpo.enabled() and mpo.scope_chunks() == 2
    assert mpo.scope_chunks() is None


# ---------------------------------------------------------------------------
# autotune family + trace-time counter
# ---------------------------------------------------------------------------

def test_mp_overlap_autotune_family_resolves():
    from paddle_tpu.distributed import mp_overlap as mpo
    from paddle_tpu.kernels import autotune as at

    key = mpo.autotune_key("row", 8, 64, 32, 2, "float32")
    fam = at.families()["mp_overlap"]
    assert fam.traceable is None        # no pallas twins (see _register)
    cands = fam.candidates(key)
    assert cands[0] == {"variant": "chunks1", "config": {"chunks": 1}}
    assert {"variant": "chunks2", "config": {"chunks": 2}} in cands
    cand = at.resolve("mp_overlap", key)
    assert cand["config"]["chunks"] >= 1
    # standard_keys carries one mp_overlap entry for the on-chip warm
    assert any(f == "mp_overlap" for f, _ in at.standard_keys())


@needs_two
def test_overlap_chunks_counter_driven():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mp_overlap as mpo
    from paddle_tpu.observability import registry as reg

    c = reg.counter("mp.overlap_chunks")
    before = c.value
    x = jax.random.normal(jax.random.key(12), (2, 8), jnp.float32)
    w = jax.random.normal(jax.random.key(13), (8, 4), jnp.float32)
    with _scoped(2, chunks=2):
        out = _jit(mpo.row_parallel_matmul)(x, w)
    assert out is not None
    assert c.value == before + 2       # one island, valued at its chunks


# ---------------------------------------------------------------------------
# tp=2 serving: bit-parity, compile-once, zero monolithic all-gather
# ---------------------------------------------------------------------------

def _engine(model, **kw):
    from paddle_tpu.serving.engine import DecodeEngine
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 16)
    return DecodeEngine(model, **kw)


def _greedy_drive(eng, prompts, steps=6):
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


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@needs_two
def test_tp2_overlapped_greedy_bit_identical():
    """THE serving acceptance criterion: at tp=2 every f32 partial sum
    has exactly two terms, so the ring's reduction commutes with
    GSPMD's — greedy tokens AND logits are bitwise equal."""
    m = _tiny_model()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, (5,)), rng.integers(0, 512, (19,))]
    base = _greedy_drive(_engine(m, seed=3, tp=2, overlap_comm=False),
                         prompts)
    eng = _engine(m, seed=3, tp=2, overlap_comm=True)
    assert eng.overlap_comm
    over = _greedy_drive(eng, prompts)
    assert eng.decode_compile_count == 1
    assert base[0] == over[0], "overlapped greedy tokens diverged"
    for b in range(len(prompts)):
        for l1, l2 in zip(base[1][b], over[1][b]):
            assert np.array_equal(l1, l2), \
                "tp=2 overlapped logits must be bit-identical"


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@needs_two
def test_tp2_overlapped_spec_int8_greedy_matches_monolithic():
    """All levers composed: overlap over the int8 pool with speculative
    verify emits the monolithic engine's exact greedy completions."""
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, (n,)) for n in (7, 13, 9)]
    results = {}
    for overlap in (False, True):
        eng = _engine(m, tp=2, spec_k=3, kv_dtype="int8", seed=0,
                      overlap_comm=overlap)
        sched = ContinuousBatchingScheduler(eng)
        rids = [sched.submit(Request(prompt=p, max_new_tokens=10))
                for p in prompts]
        res = sched.run()
        results[overlap] = [res[r].tokens.tolist() for r in rids]
    assert results[False] == results[True]


@pytest.mark.slow   # compiles the sharded decode program twice
@needs_two
def test_tp2_overlapped_hlo_zero_monolithic_allgather():
    """The structural acceptance criterion, via collective_stats'
    launches-vs-bytes split: the overlapped decode entry's partitioned
    HLO has NO all-gather and NO all-to-all, a ppermute chain instead
    — and the monolithic twin (same model, overlap off) still has the
    all-gathers, so the check can't pass vacuously."""
    import jax
    from paddle_tpu.core.dtype import x64_scope
    from paddle_tpu.observability import costs as _costs

    m = _tiny_model()
    kinds = {}
    for overlap in (False, True):
        eng = _engine(m, tp=2, overlap_comm=overlap)
        ins, outs = eng._entry_shardings["serving.decode"]
        fn = jax.jit(eng._decode_fn,
                     donate_argnums=eng._decode_donate_argnums,
                     keep_unused=True, in_shardings=ins,
                     out_shardings=outs)
        with x64_scope(False), eng._entry_scope():
            compiled = fn.lower(*eng.decode_trace_args()).compile()
        stats = _costs.collective_stats(compiled)
        assert stats is not None
        kinds[overlap] = stats["by_kind"]
    mono, over = kinds[False], kinds[True]
    assert mono.get("all-gather", {}).get("ops", 0) > 0, \
        "baseline lost its all-gathers — the structural check is vacuous"
    assert over.get("all-gather", {}).get("ops", 0) == 0
    assert over.get("all-to-all", {}).get("ops", 0) == 0
    assert over.get("collective-permute", {}).get("ops", 0) > \
        mono.get("collective-permute", {}).get("ops", 0)
    # the launches-vs-bytes split: many more launches must not read as
    # a byte blow-up (the ring moves shard-sized blocks)
    total = lambda d: sum(s["bytes"] for s in d.values())  # noqa: E731
    assert total(over) < 4 * max(total(mono), 1)


@needs_two
def test_engine_for_overlap_key_normalization(monkeypatch):
    from paddle_tpu.distributed import mp_overlap as mpo
    from paddle_tpu.serving import engine_for

    m = _tiny_model()
    monkeypatch.setenv(mpo.ENV_FLAG, "1")
    e_env = engine_for(m, num_slots=2, max_len=64, tp=2, page_size=16)
    e_arg = engine_for(m, num_slots=2, max_len=64, tp=2, page_size=16,
                       overlap_comm=True)
    assert e_env is e_arg              # one engine, one compiled program
    assert e_env.overlap_comm
    e_off = engine_for(m, num_slots=2, max_len=64, tp=2, page_size=16,
                       overlap_comm=False)
    assert e_off is not e_env and not e_off.overlap_comm
    # tp=1: the switch normalizes off even when spelled explicitly
    monkeypatch.delenv(mpo.ENV_FLAG)
    e1 = engine_for(m, num_slots=2, max_len=64, page_size=16,
                    overlap_comm=True)
    assert not e1.overlap_comm


# ---------------------------------------------------------------------------
# mp=4 training: overlapped grads match the GSPMD baseline
# ---------------------------------------------------------------------------

@pytest.mark.slow   # two full train-graph traces on the 4-device mesh
@needs_four
def test_train_grads_match_monolithic_on_mp4_mesh():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed import mp_overlap as mpo
    from paddle_tpu.distributed.parallel_base import parallelize
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    paddle.seed(11)
    cfg = GPTConfig.tiny()
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)).astype(np.int32)

    def loss_fn(st, x):
        out, _ = functional_call(model, st, paddle.Tensor(x))
        loss = crit(paddle.Tensor(out), paddle.Tensor(x))
        raw = loss._array if hasattr(loss, "_array") else loss
        return jnp.mean(raw)

    with mesh_mod.mesh_scope(_mp_mesh(4)):
        parallelize(model)         # mp pspecs need the scoped mesh
        state = model.functional_state()
        base_loss, base_g = jax.jit(jax.value_and_grad(loss_fn))(
            state, jnp.asarray(ids))
        base_loss = float(base_loss)
        base_g = jax.tree_util.tree_map(np.asarray, base_g)
        with mpo.overlap_scope(True):
            ov_loss, ov_g = jax.jit(jax.value_and_grad(loss_fn))(
                state, jnp.asarray(ids))
        ov_loss = float(ov_loss)
        ov_g = jax.tree_util.tree_map(np.asarray, ov_g)
    assert np.isfinite(base_loss) and ov_loss == pytest.approx(
        base_loss, rel=1e-6)
    flat_b, _ = jax.tree_util.tree_flatten(base_g)
    flat_o, _ = jax.tree_util.tree_flatten(ov_g)
    assert flat_b and len(flat_b) == len(flat_o)
    for gb, go in zip(flat_b, flat_o):
        np.testing.assert_allclose(go, gb, rtol=5e-4, atol=1e-5)


@needs_four
def test_mp_layers_overlap_matches_dense():
    """The Megatron layer pair with the overlap engaged equals the
    dense reference (the column/row custom_vjp forward path)."""
    import jax
    from paddle_tpu import nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed import mp_overlap as mpo
    from paddle_tpu.distributed.mp_layers import (ColumnParallelLinear,
                                                  RowParallelLinear)
    from paddle_tpu.distributed.parallel_base import parallelize
    from paddle_tpu.jit import functional_call

    paddle.seed(3)
    col = ColumnParallelLinear(16, 32, gather_output=False)
    row = RowParallelLinear(32, 8)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.col, self.row = col, row

        def forward(self, x):
            return self.row(nn.functional.relu(self.col(x)))

    mlp = MLP()
    x = paddle.randn([4, 16])
    dense_out = mlp(x).numpy()
    with mesh_mod.mesh_scope(_mp_mesh(4)):
        parallelize(mlp)
        state = mlp.functional_state()
        with mpo.overlap_scope(True):
            out, _ = jax.jit(
                lambda st, xa: functional_call(mlp, st,
                                               paddle.Tensor(xa)))(
                state, x._array)
    np.testing.assert_allclose(np.asarray(out), dense_out,
                               rtol=1e-4, atol=1e-5)
