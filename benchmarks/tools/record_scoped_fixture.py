"""Record the small chip trace kept as benchmarks/tests/fixtures/
chip_trace_scoped.xplane.pb: device work under the program's scope stamps.

    chiprun -- python benchmarks/tools/record_scoped_fixture.py

A few milliseconds with known structure, three dispatches of one program:
a two-iteration `lax.scan` opened under `lk.update`, holding a matmul under
`og.block_a`/`lk.conv`, a reduction under `og.block_b`/`lk.gn` and one
named Pallas kernel under `og.block_b`/`lk.attn`; a `tanh` of a matmul
after the loop under no scope at all. The stamps are spelled out here and
not imported, so that the tool also runs on a commit before the program's
vocabulary. The capture and its Chrome-trace twin (`*.trace.json.gz`, the
format obs/profiler.py reads: the scope path is `args.tf_op` there) come
back under chiprun_out/fixture_scoped/; the device events' metadata and
the reduction are printed.
"""
import glob
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 0.5


def body(carry, b):
    with jax.named_scope("og.block_a"), jax.named_scope("lk.conv"):
        y = jnp.dot(carry, b)
    with jax.named_scope("og.block_b"):
        with jax.named_scope("lk.gn"):
            y32 = y.astype(jnp.float32)
            mean = jnp.mean(y32, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(y32 - mean), axis=-1, keepdims=True)
            y = ((y32 - mean) * jax.lax.rsqrt(var + 1e-6)).astype(y.dtype)
        with jax.named_scope("lk.attn"):
            y = pl.pallas_call(scale_kernel, out_shape=jax.ShapeDtypeStruct(
                y.shape, y.dtype), name="scoped_fixture_scale")(y)
    return y, None


@jax.jit
def work(a, bs, c):
    with jax.named_scope("lk.update"):
        y, _ = jax.lax.scan(body, a, bs)
    return jnp.tanh(y @ c)


def main():
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_fixture needs a TPU", file=sys.stderr)
        return 3
    import scope_reduce

    out = os.path.join("chiprun_out", "fixture_scoped")
    shutil.rmtree(out, ignore_errors=True)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    bs = jnp.full((2, 1024, 1024), 1 / 32, jnp.bfloat16)
    c = jnp.ones((1024, 1024), jnp.bfloat16)
    work(a, bs, c).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("fixture_dispatch"):
            r = work(a, bs, c)
        with jax.profiler.TraceAnnotation("fixture_wait"):
            r.block_until_ready()
    jax.profiler.stop_trace()
    run = glob.glob(os.path.join(out, "plugins", "profile", "*"))[0]
    path = glob.glob(os.path.join(run, "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "chip_trace_scoped.xplane.pb"))
    print("bytes", os.path.getsize(path), os.listdir(run))
    for chip, events in scope_reduce.event_metadata(path).items():
        for name, stats in events.items():
            print("META", chip, name[:60], stats)
    red = scope_reduce.reduce(path, lambda p: ("", "unattributed"))
    print("REDUCED", json.dumps(red))
    for twin in glob.glob(os.path.join(run, "*.trace.json.gz")):
        shutil.copy(twin, os.path.join(out,
                                       "chip_trace_scoped.trace.json.gz"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
