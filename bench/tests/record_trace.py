"""Records the small trace that ``test_trace_reduce.py`` reads.

Run on a TPU host from the checkout's root:

    python bench/tests/record_trace.py

It traces three rounds of a named Pallas kernel and a jitted step inside the
harness's own spans (``bench.window`` around them all, ``bench.ingest`` and
``bench.block_ready`` around each round, a sleep between rounds for the
device to idle in), and writes ``bench/tests/data/small.xplane.pb``.
"""
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HERE = Path(__file__).resolve().parent


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


@jax.jit
def step(x):
    y = pl.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="double_kernel")(x)
    return jnp.cumsum(y, axis=0)


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    x = jnp.ones((1024, 512), jnp.float32)
    step(x).block_until_ready()
    out = HERE / "data" / "_trace"
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.ingest"):
                y = step(x)
            with jax.profiler.TraceAnnotation("bench.block_ready"):
                y.block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = sorted(out.glob("**/*.xplane.pb"))[-1]
    shutil.copy(src, HERE / "data" / "small.xplane.pb")
    shutil.rmtree(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
