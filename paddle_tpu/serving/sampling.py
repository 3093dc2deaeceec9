"""Sampling for the batched decode step — greedy / temperature / top-k /
top-p, vectorized over slots, with PER-SLOT parameters as traced arrays
so the jitted decode step never specializes on them.

Design constraints (each one is a regression test in
``tests/test_serving.py``):

* **Threaded PRNG key** — the key is an explicit argument threaded by the
  engine (``fold_in(base, step)``), never drawn from the global eager
  generator: sampling inside a compiled step must not shift the global
  RNG stream of the surrounding program (the same discipline as
  ``TrainStep.trace_args``).
* **int32-safe under the x64 audit** — paddle parity enables
  ``jax_enable_x64`` globally, so any dtype-less index math lands s64
  (flagged as s64 *compute* by the runtime HLO audit).  Token ids come
  from ``lax.top_k`` (int32 by construction — including the Gumbel-trick
  categorical, which avoids ``argmax``'s s64 result) and every index
  array is created int32.
* **top-p keeps ≥ 1 token** — the cutoff is on the *exclusive* cumulative
  mass (`mass before this token < p`), so the most-probable token always
  survives, even for ``p == 0``.
* **dynamic top-k without retracing** — ``lax.top_k`` needs a static k,
  so the kernel takes the top ``TOP_K_MAX`` once and thresholds per-slot
  at the (dynamic) k-th value; per-slot ``top_k`` stays a traced int32
  array and the decode program compiles once.

**Speculative verify (ISSUE 8).**  :func:`spec_accept` implements the
standard accept/resample rule (Leviathan et al. 2023) specialized to a
DETERMINISTIC draft (the engine's prompt-lookup proposals put
probability 1 on each drafted token): draft token ``d_j`` is accepted
with probability ``p(d_j)`` under the per-position FILTERED target
distribution (the same temperature/top-k/top-p chain :func:`sample`
uses), and a rejection resamples from ``p`` with ``d_j`` excluded — the
exact residual ``norm(max(0, p - q))`` for a point-mass ``q``, so the
output distribution is exactly the non-speculative one.  Greedy slots
(``temperature <= 0``) accept by exact argmax match, which makes greedy
output bit-identical to non-speculative decode.  All randomness comes
from ONE threaded key per iteration (two ``fold_in`` streams: the
per-draft uniforms and the bonus/correction Gumbel draw), so seed
reproducibility is independent of how many drafts are accepted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import scopes as _scopes

__all__ = ["sample", "apply_temperature", "apply_top_k", "apply_top_p",
           "filter_logits", "spec_accept", "TOP_K_MAX"]

#: static cap for per-slot top-k (requests are clamped host-side); the
#: top-TOP_K_MAX values are computed once and thresholded dynamically
TOP_K_MAX = 64

_NEG = -1e30


def apply_temperature(logits, temperature):
    """logits: (slots, vocab) — divide by per-slot temperature.  Zero (or
    negative) temperature means greedy; the division here just needs to be
    finite, :func:`sample` picks the argmax branch for those slots."""
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    return logits.astype(jnp.float32) / t[:, None]


def apply_top_k(logits, top_k, k_max=TOP_K_MAX):
    """Per-slot dynamic top-k: keep logits >= the k-th largest value;
    ``top_k <= 0`` disables filtering for that slot."""
    k_max = min(int(k_max), int(logits.shape[-1]))
    vals, _ = jax.lax.top_k(logits, k_max)   # idx unused; vals sorted desc
    kth_idx = jnp.clip(top_k.astype(jnp.int32) - 1, 0, k_max - 1)
    # promise_in_bounds (the clip above guarantees it): under global x64
    # the default gather path widens indices to s64 — the same fix as the
    # cross-entropy gather (tests/test_x64_audit.py discipline)
    kth = jnp.take_along_axis(vals, kth_idx[:, None], axis=-1,
                              mode="promise_in_bounds")
    keep = (logits >= kth) | (top_k <= 0)[:, None]
    return jnp.where(keep, logits, jnp.asarray(_NEG, logits.dtype))


def apply_top_p(logits, top_p):
    """Per-slot nucleus filtering on the softmax of ``logits``.  A token
    is kept while the probability mass STRICTLY BEFORE it (in descending
    order) is < p — so the most-probable token is always kept (`mass
    before it` is 0), the "keep at least one" guarantee.  ``top_p >= 1``
    disables filtering for that slot.  Ties at the threshold probability
    are all kept (the filter thresholds on probability values)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    sorted_p = jnp.sort(probs, axis=-1, descending=True)
    mass_before = jnp.cumsum(sorted_p, axis=-1) - sorted_p   # exclusive
    keep_sorted = mass_before < top_p.astype(jnp.float32)[:, None]
    # smallest kept probability = the per-slot threshold; the first
    # column of keep_sorted is mass_before==0 < p only when p > 0, so
    # force-keep column 0 (p == 0.0 must still emit the top token)
    keep_sorted = keep_sorted.at[:, 0].set(True)
    thresh = jnp.min(jnp.where(keep_sorted, sorted_p,
                               jnp.asarray(jnp.inf, jnp.float32)), axis=-1)
    keep = (probs >= thresh[:, None]) | (top_p >= 1.0)[:, None]
    return jnp.where(keep, logits, jnp.asarray(_NEG, logits.dtype))


def _int32_argmax(logits):
    """argmax via top_k: int32 result regardless of jax_enable_x64 (a
    bare ``jnp.argmax`` returns s64 under x64 and the cast back would
    itself be s64 compute under the HLO audit)."""
    _, idx = jax.lax.top_k(logits, 1)
    return idx[..., 0]


def filter_logits(logits, temperature, top_k, top_p, k_max=TOP_K_MAX):
    """The shared per-slot filter chain: temperature scaling, then
    top-k, then top-p — the distribution :func:`sample` draws from and
    :func:`spec_accept` accepts against."""
    scaled = apply_temperature(logits, temperature)
    return apply_top_p(apply_top_k(scaled, top_k, k_max), top_p)


def sample(logits, key, temperature, top_k, top_p, k_max=TOP_K_MAX):
    """One sampled (or greedy) token per slot.

    logits: (slots, vocab); key: a single threaded PRNG key for this
    step; temperature/top_p: (slots,) float; top_k: (slots,) int32
    (<= 0 disables).  Returns (slots,) int32 token ids.
    """
    with _scopes.scope(_scopes.SAMPLE):
        return _sample(logits, key, temperature, top_k, top_p, k_max)


def _sample(logits, key, temperature, top_k, top_p, k_max):
    greedy_tok = _int32_argmax(logits)
    filtered = filter_logits(logits, temperature, top_k, top_p, k_max)
    # Gumbel-max categorical: argmax(logits + G) ~ softmax(logits); the
    # top_k(…, 1) index is int32 by construction.  NOTE jax.random's
    # threefry loop counters follow the global x64 default — the engine
    # traces its whole entry under x64_scope(False) (the Pallas kernels'
    # discipline; a scope around just this draw would be a mid-trace x64
    # flip, which miscompiles — PERF.md/PR-1 history) so the compiled
    # decode program carries no s64 at all.
    g = jax.random.gumbel(key, filtered.shape, jnp.float32)
    sampled_tok = _int32_argmax(filtered + g)
    greedy = (temperature <= 0.0)
    return jnp.where(greedy, greedy_tok, sampled_tok).astype(jnp.int32)


def spec_accept(logits, tokens, key, temperature, top_k, top_p,
                k_max=TOP_K_MAX, max_accept=None):
    """Accept/resample for the batched speculative verify step.

    logits: (slots, k+1, vocab) — position ``j`` was scored after the
    model consumed ``tokens[:, :j+1]``; tokens: (slots, k+1) int32 =
    ``[last committed token, draft_1..draft_k]``; key: the ONE threaded
    key for this iteration; temperature/top_p: (slots,) f32; top_k:
    (slots,) int32; max_accept: optional (slots,) int32 cap on accepted
    drafts (the engine passes ``max_len - 1 - lengths`` so acceptance
    never reaches past the cache's append capacity).

    Returns ``(emitted, counts)``: emitted (slots, k+1) int32 whose row
    ``b`` holds the accepted draft tokens followed by ONE
    sampled/corrected token (zeros beyond); counts (slots,) int32 =
    accepted + 1 — both the number of usable tokens in ``emitted`` and
    the slot's in-program length advance.

    Exactness: greedy slots accept ``d_j`` iff it IS the raw-logits
    argmax at ``j`` (emitted tokens are bit-identical to sequential
    greedy decode); sampling slots accept with probability
    ``p_filtered(d_j)`` and a rejection redraws from the filtered
    distribution with ``d_j`` masked out — the exact residual for a
    deterministic draft, so every emitted token is distributed exactly
    as a non-speculative sample.  The only degenerate residual (every
    non-draft token filtered away) implies ``p_filtered(d_j) == 1``, a
    branch rejection reaches with probability 0.
    """
    S, K1, V = logits.shape
    k = K1 - 1
    greedy_tok = _int32_argmax(logits)                       # (S, K1) i32
    rep = lambda a: jnp.broadcast_to(a[:, None], (S, K1)).reshape(S * K1)
    filtered = filter_logits(
        logits.reshape(S * K1, V), rep(temperature),
        rep(top_k).astype(jnp.int32), rep(top_p),
        k_max).reshape(S, K1, V)                             # f32
    draft = tokens[:, 1:].astype(jnp.int32)                  # (S, k)
    greedy = temperature <= 0.0                              # (S,) bool
    if k:
        probs = jax.nn.softmax(filtered[:, :k, :], axis=-1)
        p_draft = jnp.take_along_axis(probs, draft[..., None], axis=-1,
                                      mode="promise_in_bounds")[..., 0]
        r = jax.random.uniform(jax.random.fold_in(key, 0), (S, k),
                               jnp.float32)
        accept = jnp.where(greedy[:, None],
                           draft == greedy_tok[:, :k],
                           r < p_draft)
        # accepted prefix length: position j survives iff ALL of 0..j do
        a0 = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                     axis=1).astype(jnp.int32)
    else:
        a0 = jnp.zeros((S,), jnp.int32)
    a = a0
    if max_accept is not None:
        a = jnp.minimum(a, jnp.maximum(max_accept.astype(jnp.int32), 0))
    # the bonus/correction token comes from position a's distribution.
    # The residual exclusion applies ONLY when the stop at `a` was a real
    # probabilistic rejection (a == a0 < k) — a capacity clamp
    # (a < a0, max_accept) stopped an ACCEPTED run, and the
    # non-speculative equivalent at that position samples from the
    # filtered distribution unmasked (masking there would bias — or,
    # under top_k=1, empty — the last token before cache_full)
    f_a = jnp.take_along_axis(filtered, a[:, None, None], axis=1,
                              mode="promise_in_bounds")[:, 0, :]  # (S, V)
    d_rej = jnp.take_along_axis(tokens.astype(jnp.int32),
                                jnp.minimum(a + 1, k)[:, None], axis=1,
                                mode="promise_in_bounds")[:, 0]
    vocab = jnp.arange(V, dtype=jnp.int32)[None, :]
    rejected_here = (a == a0) & (a0 < k)
    mask_rej = rejected_here[:, None] & (vocab == d_rej[:, None])
    f_resid = jnp.where(mask_rej, jnp.asarray(_NEG, f_a.dtype), f_a)
    g = jax.random.gumbel(jax.random.fold_in(key, 1), f_resid.shape,
                          jnp.float32)
    sampled_next = _int32_argmax(f_resid + g)
    greedy_next = jnp.take_along_axis(greedy_tok, a[:, None], axis=1,
                                      mode="promise_in_bounds")[:, 0]
    next_tok = jnp.where(greedy, greedy_next, sampled_next)
    next_tok = next_tok.astype(jnp.int32)
    # emitted row: draft[:a], then next_tok at column a, zeros beyond
    cols = jnp.arange(K1, dtype=jnp.int32)[None, :]
    draft_pad = jnp.concatenate(
        [draft, jnp.zeros((S, 1), jnp.int32)], axis=1)       # (S, K1)
    emitted = jnp.where(cols == a[:, None], next_tok[:, None], draft_pad)
    emitted = jnp.where(cols <= a[:, None], emitted,
                        jnp.zeros((), jnp.int32)).astype(jnp.int32)
    return emitted, a + jnp.ones((), jnp.int32)
