"""A/B the ring-attention INNER BLOCK on one TPU chip (r4 verdict #3):
the chunked-remat jnp blockwise scan vs the Pallas flash kernel
(flash_attention_bshd_with_lse), fwd+bwd at the long-context shard shape.

Usage: python tools/ring_inner_bench.py [seq] [heads] [steps]
Prints per-variant wall-clock over a bench.py-style many-step loop.
Also smoke-runs the FULL ring machinery (shard_map+scan+cond+ppermute with
the Pallas inner) on a 1-device 'sep' mesh so the composed program is
compiled and executed on real hardware.
"""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.ring_attention import (_blockwise_attn,
                                                       _flash_inner)

    s = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    h = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 12
    b, d = 1, 64
    scale = 1.0 / np.sqrt(d)
    on_tpu = jax.default_backend() == "tpu"
    print("backend=%s shape=(B=%d,H=%d,S=%d,D=%d)" % (
        jax.default_backend(), b, h, s, d))

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)

    def make_loop(inner, n_iter):
        """K fwd+bwd iterations CHAINED by a data dependency inside one
        jit, ending in a scalar: the wall clock covers the host pull of
        one scalar after K real iterations."""
        def loss(q_):
            out, lse = inner(q_, k, v)
            return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(lse)
        gfn = jax.grad(loss)

        @jax.jit
        def loop(q0):
            def body(_, qq):
                return qq + 1e-6 * gfn(qq).astype(qq.dtype)
            qn = jax.lax.fori_loop(0, n_iter, body, q0)
            return jnp.sum(qn.astype(jnp.float32))
        return loop

    variants = {
        "jnp_blockwise": lambda q_, k_, v_: _blockwise_attn(
            q_, k_, v_, jnp.float32(scale), jnp.int32(0), jnp.int32(0),
            True, None, 512),
        "pallas_flash": lambda q_, k_, v_: _flash_inner(
            q_, k_, v_, True, float(scale)),
    }
    results = {}
    for name, inner in variants.items():
        try:
            loop = make_loop(inner, steps)
            float(loop(q))                # compile + warmup (full chain)
            t0 = time.perf_counter()
            float(loop(q))                # one host-pulled scalar
            dt = (time.perf_counter() - t0) / steps
            results[name] = dt
            print("%-14s %8.2f ms/iter (fwd+bwd, %d chained steps)"
                  % (name, dt * 1e3, steps))
        except Exception as e:
            print("%-14s FAILED: %s" % (name, str(e)[:200]))
    if len(results) == 2:
        print("speedup pallas vs jnp: %.2fx"
              % (results["jnp_blockwise"] / results["pallas_flash"]))

    # composed-path smoke: the real ring program with the Pallas inner on
    # a 1-device 'sep' mesh (scan+cond+ppermute+pallas in ONE program)
    if on_tpu:
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec

        from paddle_tpu.distributed.ring_attention import ring_attention
        mesh = Mesh(np.array(jax.devices()[:1]), ("sep",))
        ring = shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, "sep",
                                              causal=True),
            mesh=mesh,
            in_specs=(PartitionSpec(None, None, "sep", None),) * 3,
            out_specs=PartitionSpec(None, None, "sep", None))
        sq = q[:, :, :2048]
        out = jax.jit(ring)(sq, sq, sq)
        jax.block_until_ready(out)
        print("ring(sep=1, pallas inner) composed-program smoke: ok",
              out.shape, out.dtype)


if __name__ == "__main__":
    main()
