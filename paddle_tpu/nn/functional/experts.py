"""Routed-expert functionals: a dropless top-k expert layer that is told
which experts it holds.

The router scores every token against ALL the experts of the model
(``router_width``), as every chip of an expert-parallel group does; this
chip computes the part of the result that the experts it holds give, for
the tokens routed to them.  What the absent experts would add is another
chip's part (on one chip there is no exchange, and nothing stands in for
it).

Two routers and two expert forms are the options (a layer names one of
each: ``nn.layer.experts.RoutedExperts``):

* :func:`route_raw` — the sigmoid router (DeepSeek-V3's, as Nemotron-H takes
  it): sigmoid scores in float32, the top k of ``score + bias``, weights
  ``score / (sum of the chosen scores + 1e-20) * scale``;
* :func:`route_softmax_raw` — the softmax router (Qwen3-Next's): a float32
  softmax over all the experts, its top k, weights ``p / (sum of the chosen
  p)``;
* :func:`gate_shared_raw` — the shared expert's gate, ``sigmoid(x . w)``;
* :func:`held_experts_raw` — the assignments that fall on held experts,
  sorted by expert into a buffer, through the experts and added back to
  their tokens.  ``w_up`` and ``w_down`` alone are squared-ReLU experts (a
  grouped product up, ``relu(.)^2``, a grouped product down); with
  ``w_gate`` they are gated ones (``down(silu(gate(x)) * up(x))``: three
  grouped products).
  No capacity: a token picks at most ``min(k, held)`` experts here, so
  ``tokens * min(k, held)`` rows always suffice.  The buffer is launched
  with :func:`usual_rows` rows, three times what uniform routing sends
  here; when a step's routing does not fit (a ``lax.cond`` on the count)
  it takes the worst case in one launch, or, where the worst case's
  buffers are gigabytes (a 16k-token row), the usual launch over one
  window of the sorted assignments after the other until all are through:
  no assignment is ever dropped.  The grouped
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


def route_softmax_raw(x, router_weight, top_k):
    """x (T, h), router_weight (h, E) float32 -> (chosen (T, k) int32 expert
    ids, weights (T, k) float32): the top k of a float32 softmax over all E
    experts, renormalised over the chosen.  The product runs at the highest
    precision, as :func:`route_raw`'s."""
    logits = jnp.matmul(x.astype(F32), router_weight.astype(F32),
                        precision=jax.lax.Precision.HIGHEST)
    picked, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return chosen.astype(I32), picked / jnp.sum(picked, axis=-1,
                                                keepdims=True)


def gate_shared_raw(shared, x, w):
    """``sigmoid(x . w) * shared`` a token: the shared expert's gate, x (...,
    h) against w (h,), in float32, ``shared``'s type out."""
    gate = jax.nn.sigmoid(jnp.sum(x.astype(F32) * w.astype(F32), axis=-1,
                                  keepdims=True))
    return (shared.astype(F32) * gate).astype(shared.dtype)


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
    balancer; PERF.md section 6.)  The tile and the factor were set for
    that cell's 8 wide experts (384 rows each); the 16k-token cell of 32
    narrow experts, 10 of 512 a token, expects 10,240 rows (320 an expert)
    and launches 30,720 of a worst case of 163,840: ``moe_padded_rows_pct
    .train`` reads 66.7 there too (PERF.md section 6, PR 35).  Neither was
    changed for it."""
    worst = tokens * min(top_k, held)
    expected = tokens * top_k * held / width
    return min(worst, math.ceil(3 * expected / tile) * tile)


def _sorted_part(x, local, weights, experts, rows, start=None):
    """The held experts' part through a sorted buffer of ``rows`` rows;
    exact as long as the assignments on held experts fit in it.
    ``experts``: ``(w_up, w_down)`` or ``(w_gate, w_up, w_down)``.  With
    ``start`` (a traced int32) the buffer holds the sorted assignments
    ``start .. start + rows - 1`` instead of the first ``rows``."""
    tokens, k = local.shape
    *w_gate, w_up, w_down = experts
    held = w_up.shape[0]
    flat = local.reshape(-1)
    # stable: inside an expert's group the tokens stay in order (int32
    # keys and positions: under x64 an argsort's would be 64-bit)
    expert, order = jax.lax.sort(
        (flat, jnp.arange(flat.shape[0], dtype=I32)), num_keys=1,
        is_stable=True)
    if start is None:
        expert, order = expert[:rows], order[:rows]
    else:       # past the last assignment: rows of no expert
        pad = (0, -flat.shape[0] % rows)
        expert = jax.lax.dynamic_slice(
            jnp.pad(expert, pad, constant_values=held), (start,), (rows,))
        order = jax.lax.dynamic_slice(jnp.pad(order, pad), (start,), (rows,))
    valid = (expert < held)[:, None]
    token = order // k
    sizes = jnp.sum(flat[:, None] == jnp.arange(held, dtype=I32),
                    axis=0, dtype=I32)
    if start is not None:       # what of each expert's run the window holds
        ends = jnp.cumsum(sizes, dtype=I32)
        sizes = (jnp.clip(ends, start, start + rows)
                 - jnp.clip(ends - sizes, start, start + rows))
    xs = jnp.where(valid, x[token], jnp.zeros((), x.dtype))
    with _scopes.scope(_scopes.MOE_EXPERTS):
        up = grouped_matmul(xs, w_up, sizes)
        if w_gate:
            act = (jax.nn.silu(grouped_matmul(xs, w_gate[0], sizes)
                               .astype(F32)) * up.astype(F32)
                   ).astype(up.dtype)
        else:
            act = jnp.square(jax.nn.relu(up))
        down = grouped_matmul(act, w_down, sizes, F32)
    gate = weights.reshape(-1)[order]
    down = jnp.where(valid, down * gate[:, None], 0.0)
    return jnp.zeros((tokens, x.shape[1]), F32).at[token].add(down)


def _held_count(local, held):
    return jnp.sum(local < held, dtype=I32)


def _fits(local, held, rows):
    return _held_count(local, held) <= rows


def _windows(local, held, rows):
    """How many windows of ``rows`` sorted assignments it takes to pass
    every assignment on a held expert (they sort in front of the others)."""
    return (_held_count(local, held) + rows - 1) // rows


def _every_window(x, local, weights, experts, rows):
    """The dropless fallback: :func:`_sorted_part` over one window of
    ``rows`` sorted assignments after the other until every assignment on
    a held expert is through.  Exact for any routing, at the memory of one
    launch of ``rows`` rows (the worst case in one launch, ``tokens *
    min(k, held)`` rows, keeps buffers of gigabytes in the program of a
    16k-token step whether it runs or not) and at a cost that follows how
    far the step's routing overflowed."""
    return jax.lax.fori_loop(
        0, _windows(local, experts[-1].shape[0], rows),
        lambda i, out: out + _sorted_part(x, local, weights, experts, rows,
                                          i * rows),
        jnp.zeros((local.shape[0], x.shape[1]), F32))


def _every_window_grads(x, local, weights, experts, rows, grad):
    """The gradients of :func:`_every_window` for x, weights and experts:
    the windows' parts add up, so their gradients do, a window at a time
    (float32 sums, the operands' types out)."""
    operands = (x, weights, experts)

    def window(i, sums):
        _, vjp = jax.vjp(
            lambda x, wt, ex: _sorted_part(x, local, wt, ex, rows, i * rows),
            *operands)
        return jax.tree_util.tree_map(lambda s, g: s + g.astype(F32), sums,
                                      vjp(grad))
    sums = jax.lax.fori_loop(
        0, _windows(local, experts[-1].shape[0], rows), window,
        jax.tree_util.tree_map(lambda t: jnp.zeros(t.shape, F32), operands))
    return jax.tree_util.tree_map(lambda s, t: s.astype(t.dtype), sums,
                                  operands)


#: the most one launch of the dropless worst case may ask for its float32
#: output rows (``tokens * min(k, held)`` rows of the hidden width): past
#: it the fallback goes window by window (:func:`_every_window`)
_ONE_LAUNCH_BYTES = 2 ** 30


def _windowed(worst, hidden):
    """Whether the fallback of a layer whose worst case is ``worst`` rows
    runs window by window.  One launch of the worst case is the cheaper
    step where its buffers are small (the 8k-token cell: 49,152 rows of
    2,688, 0.5 GiB; a ``while`` in the branch cost that cell 2.7% of its
    tokens a second, PERF.md section 6, PR 35); at 163,840 rows of 2,048
    (1.25 GiB a buffer, 4.3 GiB in the backward) the program did not fit
    beside the model."""
    return worst * hidden * 4 > _ONE_LAUNCH_BYTES


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _two_launches(x, local, weights, experts, usual, worst):
    """:func:`_sorted_part` over ``usual`` rows where the held assignments
    fit in them; where they do not, over the dropless ``worst`` case in one
    launch, or (:func:`_windowed`) over window after window of ``usual``
    rows: one ``lax.cond``, so that no assignment is ever dropped and the
    usual step does not pay for the worst."""
    if _windowed(worst, x.shape[1]):
        overflow = lambda *a: _every_window(*a, usual)
    else:
        overflow = lambda *a: _sorted_part(*a, worst)
    return jax.lax.cond(
        _fits(local, experts[-1].shape[0], usual),
        lambda *a: _sorted_part(*a, usual), overflow,
        x, local, weights, experts)


def _two_launches_fwd(x, local, weights, experts, usual, worst):
    return (_two_launches(x, local, weights, experts, usual, worst),
            (x, local, weights, experts))


def _two_launches_bwd(usual, worst, residuals, grad):
    # each branch makes its own forward again and takes its gradient
    # there: differentiating the cond itself would keep, for the branch
    # not taken, zeros the size of the worst case's buffers
    x, local, weights, experts = residuals

    def branch(rows):
        def run(x, weights, experts, grad):
            _, vjp = jax.vjp(
                lambda x, wt, ex: _sorted_part(x, local, wt, ex, rows),
                x, weights, experts)
            return vjp(grad)
        return run
    if _windowed(worst, x.shape[1]):
        overflow = lambda x, wt, ex, grad: _every_window_grads(
            x, local, wt, ex, usual, grad)
    else:
        overflow = branch(worst)
    d_x, d_weights, d_experts = jax.lax.cond(
        _fits(local, experts[-1].shape[0], usual), branch(usual), overflow,
        x, weights, experts, grad)
    return d_x, None, d_weights, d_experts


_two_launches.defvjp(_two_launches_fwd, _two_launches_bwd)


def held_experts_raw(x, local, weights, w_up, w_down, usual=None,
                     w_gate=None):
    """The held experts' part of the layer's output, (T, h) float32.

    x (T, h); local (T, k) int32 positions among the held experts (``H =
    w_up.shape[0]`` for an expert held elsewhere); weights (T, k) float32;
    w_up (H, h, f), w_down (H, f, h): squared-ReLU experts, or with w_gate
    (H, h, f) gated ones.  The assignments on held experts are sorted by
    expert into a buffer, go through the grouped products (``moe_experts``
    in a trace, apart from the routing around them), and are added back to
    their tokens.  The buffer has ``usual`` rows (:func:`usual_rows`); a
    step whose held assignments do not fit in them takes the dropless worst
    case ``T * min(k, H)`` in one launch where that is small, and where it
    is not (:func:`_windowed`) goes through one window of ``usual`` sorted
    assignments after the other (:func:`_every_window`); ``usual=None``
    always launches the worst case at once."""
    tokens, k = local.shape
    experts = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    worst = tokens * min(k, experts[-1].shape[0])
    if usual is None or usual >= worst:
        return _sorted_part(x, local, weights, experts, worst)
    return _two_launches(x, local, weights, experts, usual, worst)
