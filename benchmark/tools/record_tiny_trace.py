"""Record the small trace kept under benchmark/tests/data/: a few executions
of one tiny jitted program, each inside a ``bench.step`` span with a
``bench.sleep`` between. Run on the chip; writes chiprun_out/tiny.xplane.pb."""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from benchmark import trace as T  # noqa: E402
from benchmark.loadgen import span  # noqa: E402


def main():
    def step(x):
        return jnp.tanh(x @ x).sum()
    f = jax.jit(step)
    x = jnp.ones((256, 256), jnp.bfloat16)
    f(x).block_until_ready()
    d = os.path.join(ROOT, ".bench_trace_tiny")
    shutil.rmtree(d, ignore_errors=True)
    T.start(d)
    for _ in range(5):
        with span("bench.step"):
            f(x).block_until_ready()
        with span("bench.sleep"):
            time.sleep(0.002)
    path = T.stop(d)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "tiny.xplane.pb"))
    print(os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main()
