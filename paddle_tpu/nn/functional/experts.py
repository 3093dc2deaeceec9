"""Routed-expert functionals: a dropless top-k expert layer that is told
which experts it holds.

The router scores every token against ALL the experts of the model
(``router_width``), as every chip of an expert-parallel group does; this
chip computes the part of the result that the experts it holds give, for
the tokens routed to them.  What the absent experts would add is another
chip's part (on one chip there is no exchange, and nothing stands in for
it).

* :func:`route_raw` — sigmoid scores in float32, the top k of ``score +
  bias``, weights ``score / (sum of the chosen scores + 1e-20) * scale``;
* :func:`held_experts_raw` — the assignments that fall on held experts,
  sorted by expert into a buffer, through a grouped matrix product up,
  ``relu(.)^2``, a grouped product down, and added back to their tokens.
  No capacity: a token picks at most ``min(k, held)`` experts here, so
  ``tokens * min(k, held)`` rows always suffice.  The buffer is launched
  with :func:`usual_rows` rows, three times what uniform routing sends
  here, and with the worst case when a step's routing does not fit (a
  ``lax.cond`` on the count): no assignment is ever dropped.  The grouped
  products are ``kernels.grouped_matmul`` (megablox on a TPU), which
  visits the row tiles the assignments cover and no others: the rows of
  the launch past them cost a gather and a mask, and a step's time follows
  where the router sent its tokens.

Raw functions over jax arrays; ``nn.layer.experts`` is the layer.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...kernels.grouped_matmul import grouped_matmul
from ...observability import scopes as _scopes

F32 = jnp.float32
I32 = jnp.int32


def note_call(path: str, tokens: int, top_k: int, held: int, width: int,
              launched: int) -> None:
    """Drive ``moe.calls{path}`` and ``moe.rows{which}`` at trace time, one
    inc per expert layer traced: ``routed`` tokens x k assignments,
    ``expected_held`` of them on this chip's experts under uniform routing,
    ``launched`` rows the grouped products are launched over in a step
    whose routing fits them."""
    try:
        from ...observability import registry as _reg
        _reg.counter("moe.calls", ("path",)).labels(path=path).inc()
        rows = _reg.counter("moe.rows", ("which",))
        rows.labels(which="routed").inc(tokens * top_k)
        rows.labels(which="expected_held").inc(
            tokens * top_k * held // width)
        rows.labels(which="launched").inc(launched)
    except Exception:
        pass


def route_raw(x, router_weight, bias, top_k, scale):
    """x (T, h), router_weight (h, E) and bias (E,) float32 -> (chosen
    (T, k) int32 expert ids, weights (T, k) float32).  The product runs at
    the highest precision: a score rounded to bf16 picks another expert at
    a near-tie, and that moves a token's output by a whole expert."""
    logits = jnp.matmul(x.astype(F32), router_weight.astype(F32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(I32), weights * scale


def local_ids(chosen, held, width):
    """Expert ids (T, k) -> positions in ``held`` (a tuple of the expert
    ids this chip holds), ``len(held)`` where the expert is another
    chip's."""
    table = jnp.full((width,), len(held), I32).at[
        jnp.asarray(held, I32)].set(jnp.arange(len(held), dtype=I32))
    return table[chosen]


def usual_rows(tokens, top_k, held, width, tile=512):
    """Rows the sorted buffer is launched with: three times what uniform
    routing sends to the held experts, in whole tiles, and never more than
    the dropless worst case ``tokens * min(top_k, held)``.  (Routers are
    far from uniform: over 12 seeds of the 8k-token cell a layer's held
    experts got 0.51 to 1.41 times their uniform share at initialisation,
    and up to 2.4 times in the first twenty steps of AdamW with no load
    balancer; PERF.md section 6.)"""
    worst = tokens * min(top_k, held)
    expected = tokens * top_k * held / width
    return min(worst, math.ceil(3 * expected / tile) * tile)


def _sorted_part(x, local, weights, w_up, w_down, rows):
    """The held experts' part through a sorted buffer of ``rows`` rows;
    exact as long as the assignments on held experts fit in it."""
    tokens, k = local.shape
    held = w_up.shape[0]
    flat = local.reshape(-1)
    # stable: inside an expert's group the tokens stay in order (int32
    # keys and positions: under x64 an argsort's would be 64-bit)
    expert, order = jax.lax.sort(
        (flat, jnp.arange(flat.shape[0], dtype=I32)), num_keys=1,
        is_stable=True)
    expert, order = expert[:rows], order[:rows]
    valid = (expert < held)[:, None]
    token = order // k
    sizes = jnp.sum(flat[:, None] == jnp.arange(held, dtype=I32),
                    axis=0, dtype=I32)
    xs = jnp.where(valid, x[token], jnp.zeros((), x.dtype))
    with _scopes.scope(_scopes.MOE_EXPERTS):
        up = grouped_matmul(xs, w_up, sizes)
        act = jnp.square(jax.nn.relu(up))
        down = grouped_matmul(act, w_down, sizes, F32)
    gate = weights.reshape(-1)[order]
    down = jnp.where(valid, down * gate[:, None], 0.0)
    return jnp.zeros((tokens, x.shape[1]), F32).at[token].add(down)


def _fits(local, held, rows):
    return jnp.sum(local < held, dtype=I32) <= rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _two_launches(x, local, weights, w_up, w_down, usual, worst):
    """:func:`_sorted_part` over ``usual`` rows where the held assignments
    fit in them, over the dropless ``worst`` case where they do not: one
    ``lax.cond``, so that no assignment is ever dropped and the usual step
    does not pay for the worst."""
    return jax.lax.cond(
        _fits(local, w_up.shape[0], usual),
        lambda *a: _sorted_part(*a, usual),
        lambda *a: _sorted_part(*a, worst), x, local, weights, w_up, w_down)


def _two_launches_fwd(x, local, weights, w_up, w_down, usual, worst):
    return (_two_launches(x, local, weights, w_up, w_down, usual, worst),
            (x, local, weights, w_up, w_down))


def _two_launches_bwd(usual, worst, residuals, grad):
    # each branch makes its own forward again and takes its gradient
    # there: differentiating the cond itself would keep, for the branch
    # not taken, zeros the size of the worst case's buffers
    x, local, weights, w_up, w_down = residuals

    def branch(rows):
        def run(x, weights, w_up, w_down, grad):
            _, vjp = jax.vjp(
                lambda x, wt, wu, wd: _sorted_part(x, local, wt, wu, wd,
                                                   rows),
                x, weights, w_up, w_down)
            return vjp(grad)
        return run
    d_x, d_weights, d_up, d_down = jax.lax.cond(
        _fits(local, w_up.shape[0], usual), branch(usual), branch(worst),
        x, weights, w_up, w_down, grad)
    return d_x, None, d_weights, d_up, d_down


_two_launches.defvjp(_two_launches_fwd, _two_launches_bwd)


def held_experts_raw(x, local, weights, w_up, w_down, usual=None):
    """The held experts' part of the layer's output, (T, h) float32.

    x (T, h); local (T, k) int32 positions among the held experts (``H =
    w_up.shape[0]`` for an expert held elsewhere); weights (T, k) float32;
    w_up (H, h, f), w_down (H, f, h).  The assignments on held experts are
    sorted by expert into a buffer, go through a grouped product up,
    ``relu(.)^2`` and a grouped product down (``moe_experts`` in a trace,
    apart from the routing around them), and are added back to their
    tokens.  The buffer has ``usual`` rows (:func:`usual_rows`) where they
    fit and the dropless worst case ``T * min(k, H)`` where they do not;
    ``usual=None`` always launches the worst case."""
    tokens, k = local.shape
    worst = tokens * min(k, w_up.shape[0])
    if usual is None or usual >= worst:
        return _sorted_part(x, local, weights, w_up, w_down, worst)
    return _two_launches(x, local, weights, w_up, w_down, usual, worst)
