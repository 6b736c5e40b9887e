"""Record the small profiler trace that ``test_trace_reduce.py`` reads, on
the chip:

    python3 bench/tests/record_trace.py bench/tests/data/small_trace.xplane.pb

Inside a ``bench.window`` annotation it runs a jitted matrix product
twelve times: eight times inside a ``bench.serve.step`` annotation, each
followed by an un-annotated 20 ms sleep, then four times followed by a
30 ms sleep inside ``bench.serve.wait``. It prints the planes and lines it
recorded, which the test's expectations are written from.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(8):
            with jax.profiler.TraceAnnotation("bench.serve.step"):
                f(x).block_until_ready()
            time.sleep(0.02)
        for _ in range(4):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.serve.wait"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(found[0], out)
    shutil.rmtree(d)
    from jax.profiler import ProfileData
    for p in ProfileData.from_file(out).planes:
        for ln in p.lines:
            ev = list(ln.events)
            print(p.name, "|", ln.name, "|", len(ev), "|",
                  [(e.name, e.start_ns, e.duration_ns) for e in ev[:4]])
    print("bytes", os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
