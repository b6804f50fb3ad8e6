"""Record the small chip trace kept as benchmarks/tests/fixtures/
chip_trace.xplane.pb, and print its structure.

    chiprun -- python benchmarks/tools/record_fixture.py

A few milliseconds of device work with known structure: XLA fusions, one
Pallas kernel (a `tpu_custom_call`), host annotations around each
dispatch and a host sleep between dispatches (a device idle gap with a
known owner). The trace comes back under chiprun_out/fixture/.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def work(a, b):
    y = jnp.tanh(a @ b)
    y = pl.pallas_call(scale_kernel, out_shape=jax.ShapeDtypeStruct(
        y.shape, y.dtype), name="bench_fixture_scale")(y)
    return (y @ b).sum()


def main():
    if jax.devices()[0].platform != "tpu":
        print("record_fixture needs a TPU", file=sys.stderr)
        return 3
    out = os.path.join("chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    work(a, b).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(4):
        with jax.profiler.TraceAnnotation("fixture_dispatch"):
            r = work(a, b)
        with jax.profiler.TraceAnnotation("fixture_wait"):
            r.block_until_ready()
        with jax.profiler.TraceAnnotation("fixture_sleep"):
            time.sleep(0.004)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "chip_trace.xplane.pb"))
    print("bytes", os.path.getsize(path))
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                stats = {k: (str(v)[:60]) for k, v in ev.stats}
                print("    EV", repr(ev.name)[:80], ev.start_ns,
                      ev.duration_ns, stats)
    return 0


if __name__ == "__main__":
    sys.exit(main())
