"""Chip probe of the kernels in front of the recurrent scans (``paddle_tpu/
kernels/causal_conv.py``) at the shapes of ``train_nemo3nano_s8192`` and
``train_qwen3next_s16384``: one layer's convolution + SiLU + split
(+ normalisation), forward and forward-and-backward, the kernels against the
``jnp`` path, in ms and in GB/s of the bytes the work needs (the convolved
columns read and the parts written once forward; the cotangents and the
columns read, the columns' gradient written backward); and how far the two
paths' numbers stand apart on the chip's own arithmetic.

``python tools/mixer_conv_probe.py [--rehearse] [--blocks 512x512,...]``;
one JSON line a reading, the whole in ``chiprun_out/mixer_conv_probe.jsonl``.
``--bundles`` needs no chip: it compiles each kernel for a described v5e
under the compiler's dump flag (a child process a compile, which aborts
after it) and counts the VLIW bundles of the kernel's body and the slots
they use: a count, never a time."""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import causal_conv as K
from paddle_tpu.nn.functional import ssm as FS

LINES = []
# (tokens, the projection's width, offset, parts, bias?)
FORMS = {
    "nemo3nano": (8192, 10304, 4096,
                  ((4096, None, 1.0), (1024, None, 1.0), (1024, None, 1.0)),
                  True),
    "qwen3next": (16384, 12288, 0,
                  ((2048, 128, 128 ** -0.5), (2048, 128, 1.0),
                   (4096, None, 1.0)), False),
}
TINY = {
    "nemo3nano": (64, 1088, 512,
                  ((256, None, 1.0), (128, None, 1.0), (128, None, 1.0)),
                  True),
    "qwen3next": (64, 896, 0, ((256, 128, 128 ** -0.5), (256, 128, 1.0),
                               (128, None, 1.0)), False),
}


def say(**reading):
    LINES.append(reading)
    print(json.dumps(reading), flush=True)


def inputs(form, seed=1, dtype=jnp.bfloat16):
    seq, width, _, parts, bias = form
    channels = sum(p[0] for p in parts)
    rng = np.random.default_rng(seed)
    proj = jnp.asarray(rng.normal(0, 1.0, (1, seq, width)), dtype)
    taps = jnp.asarray(rng.uniform(-0.5, 0.5, (4, channels)), dtype)
    b = jnp.asarray(rng.normal(0, 0.2, (channels,)), dtype) if bias else None
    return proj, taps, b


def paths(form, interpret):
    _, _, offset, parts, _ = form

    def kernel(proj, taps, b):
        return K.conv_split(proj, taps, b, offset, parts, True, interpret)

    def plain(proj, taps, b):
        return FS._conv_split_jnp(proj, offset, parts, taps, b, True)
    return {"kernel": kernel, "jnp": plain}


def loss_of(fn):
    return lambda *a: sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in fn(*a))


def clock(fn, args, reps):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def times(name, form, interpret, reps, tag):
    seq, _, _, parts, bias = form
    args = inputs(form)
    wrt = (0, 1, 2) if bias else (0, 1)
    moved = 2 * seq * sum(p[0] for p in parts)      # bf16 bytes of a pass
    for path, fn in paths(form, interpret).items():
        if path == "jnp" and tag != "default":
            continue
        fwd = clock(jax.jit(fn), args, reps)
        both = clock(jax.jit(jax.grad(loss_of(fn), argnums=wrt)), args, reps)
        say(reading="time_ms", cell=name, path=path, blocks=tag, forward=fwd,
            forward_and_backward=both, backward=both - fwd,
            forward_gb_s=2 * moved / fwd / 1e6,
            backward_gb_s=3 * moved / max(both - fwd, 1e-9) / 1e6)


def distance(name, form, interpret):
    """The two paths on the same inputs: the share of the parts' elements
    that differ at all and by more than one unit in the last place of the
    activations' type, and the gradients' relative distance."""
    args = inputs(form, seed=3)
    wrt = (0, 1, 2) if form[4] else (0, 1)
    got = {path: (jax.jit(fn)(*args),
                  jax.jit(jax.grad(loss_of(fn), argnums=wrt))(*args))
           for path, fn in paths(form, interpret).items()}
    for i, (a, b) in enumerate(zip(got["kernel"][0], got["jnp"][0])):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        say(reading="parts_apart", cell=name, part=i,
            differ_share=float(jnp.mean(a != b)),
            past_one_ulp_share=float(jnp.mean(
                jnp.abs(a - b) > 2.0 ** -7 * jnp.abs(b))),
            max_abs=float(jnp.max(jnp.abs(a - b))))
    rel = lambda a, b: float(
        jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
        / jnp.linalg.norm(b.astype(jnp.float32)))
    say(reading="gradients_apart", cell=name, **{
        n: rel(a, b) for n, a, b in zip(("dproj", "dtaps", "dbias"),
                                        got["kernel"][1], got["jnp"][1])})


def compile_one(name, way):
    """Compile one cell's kernels one way for a described v5e (the child of
    ``--bundles``: the dump flag makes the process abort afterwards)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    seq, width, _, parts, bias = form = FORMS[name]
    channels = sum(p[0] for p in parts)
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=chip)
    args = [spec(1, seq, width), spec(4, channels),
            spec(channels) if bias else None]
    fn = paths(form, False)["kernel"]
    if way == "backward":
        fn = jax.grad(loss_of(fn), argnums=(0, 1, 2) if bias else (0, 1))
    jax.jit(fn).lower(*args).compile()


def bundles():
    units = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
             "VSTORE:SPILL", "SALU")
    for name in FORMS:
        for way in ("forward", "backward"):
            out = tempfile.mkdtemp(prefix="mixer_conv_dump_")
            try:
                subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--compile-one", name, way],
                    env={**os.environ, "JAX_PLATFORMS": "cpu",
                         "LIBTPU_INIT_ARGS": "--xla_jf_dump_to=" + out},
                    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
                kernel = "causal_conv_" + way[:1] + "wd"
                for path in sorted(glob.glob(os.path.join(
                        out, "*-%s*-final_hlo-static-per-bundle-"
                        "utilization.txt" % kernel))):
                    rows = [line.split() for line in open(path)][4:]
                    rows = [r for r in rows if len(r) == len(units)]
                    say(reading="bundles", cell=name, way=way,
                        launch=os.path.basename(path).split("-")[1],
                        bundles=len(rows), **{
                            u: sum(int(r[i]) for r in rows)
                            for i, u in enumerate(units)})
            finally:
                shutil.rmtree(out, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bundles", action="store_true")
    ap.add_argument("--compile-one", nargs=2, metavar=("CELL", "WAY"))
    ap.add_argument("--blocks", default="",
                    help="tokens x channels a grid step takes at most, a "
                         "comma between, beside the default")
    opt = ap.parse_args()
    if opt.compile_one:
        return compile_one(*opt.compile_one)
    if opt.bundles:
        bundles()
    else:
        interpret = opt.rehearse
        if not interpret and jax.default_backend() != "tpu":
            sys.exit("no TPU: --rehearse runs the same code tiny in the "
                     "interpreter, --bundles counts the compiler's bundles")
        forms, reps = (TINY, 1) if interpret else (FORMS, 20)
        say(reading="device", platform=jax.devices()[0].platform,
            kind=jax.devices()[0].device_kind, rehearsal=interpret)
        for name, form in forms.items():
            distance(name, form, interpret)
            times(name, form, interpret, reps, "default")
            for block in filter(None, opt.blocks.split(",")):
                saved = K._BLOCK_TOKENS, K._BLOCK_CHANNELS
                K._BLOCK_TOKENS, K._BLOCK_CHANNELS = map(int,
                                                         block.split("x"))
                try:
                    times(name, form, interpret, reps, block)
                finally:
                    K._BLOCK_TOKENS, K._BLOCK_CHANNELS = saved
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mixer_conv_probe.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in LINES)


if __name__ == "__main__":
    main()
