"""Record the small profiler trace that ``test_bench_trace_reduce.py`` reads.

Run once on a TPU (``python3 tests/bench/record_trace_fixture.py <out_dir>``):
four steps, each a 20 ms host span named ``prepare`` in which the device is
idle, then one jitted matmul step waited on; all inside the harness's window
span. The ``.xplane.pb`` lands under ``<out_dir>`` and a short description
of its planes and lines is printed.
"""
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


def main(out_dir: str) -> None:
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 2.0)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with TraceAnnotation("bench_window"):
        for _ in range(4):
            with TraceAnnotation("prepare"):
                time.sleep(0.02)
            step(x).block_until_ready()
    jax.profiler.stop_trace()
    import glob
    path = glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True)[0]
    print("trace", path, flush=True)
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("plane", repr(plane.name), [(ln.name, len(list(ln.events))) for ln in lines])
        for ln in lines:
            for e in list(ln.events)[:4]:
                print("   ", repr(ln.name), repr(e.name), e.start_ns, e.duration_ns)


if __name__ == "__main__":
    main(sys.argv[1])
