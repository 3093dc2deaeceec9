"""TPU503 fixtures: cond branches with mismatched collective sequences
(the shard_map deadlock class), an undeclared shard_map axis, an
out-of-range ppermute — and a healthy uniform program as the negative."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.analysis.trace import TraceProgram


def build_programs():
    devices = jax.devices()
    n = min(4, len(devices))
    mesh = Mesh(np.asarray(devices[:n]), ("dp",))

    def branch_mismatch(x):
        # one branch psums, the other doesn't: if the predicate ever
        # diverges across devices the psum branch blocks forever
        return jax.lax.cond(x.sum() > 0,
                            lambda a: jax.lax.psum(a, "dp"),
                            lambda a: a * 2.0, x)

    def uniform(x):
        # both branches issue the same collective sequence
        return jax.lax.cond(x.sum() > 0,
                            lambda a: jax.lax.psum(a, "dp"),
                            lambda a: jax.lax.psum(a * 2.0, "dp"), x)

    def bad_perm(x):
        # pair targets device index n (one past the end of the axis)
        return jax.lax.ppermute(x, "dp", perm=[(0, n)])

    def sm(fn):
        return shard_map(fn, mesh=mesh, in_specs=(P("dp"),),
                         out_specs=P("dp"), check_vma=False)

    x = jnp.ones((n * 2, 4), jnp.float32)
    declared = {"mesh_axes": {"dp": n}, "kind": "fixture"}
    return [
        TraceProgram(name="fixture/tpu503_branch_mismatch",
                     jaxpr=jax.make_jaxpr(sm(branch_mismatch))(x),
                     meta=dict(declared)),
        TraceProgram(name="fixture/tpu503_ok",
                     jaxpr=jax.make_jaxpr(sm(uniform))(x),
                     meta=dict(declared)),
        TraceProgram(name="fixture/tpu503_bad_perm",
                     jaxpr=jax.make_jaxpr(sm(bad_perm))(x),
                     meta=dict(declared)),
        # the traced mesh axis ('dp') is not what the program declares it
        # deploys on ('pp') — topology drift
        TraceProgram(name="fixture/tpu503_undeclared_axis",
                     jaxpr=jax.make_jaxpr(sm(uniform))(x),
                     meta={"mesh_axes": {"pp": n}, "kind": "fixture"}),
    ]
