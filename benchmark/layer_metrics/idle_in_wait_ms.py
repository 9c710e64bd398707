"""Device: idle time of the device (gaps of ``XLA Ops``, device 0) while the
host was blocked on it: the gaps intersected with ``engine.chunk_wait`` and
``engine.decode_wait``, per ``engine.step`` span that holds an
``engine.dispatch`` in the trace: launch and readback latency, which no host
timer sees. Left out without a device plane, or where the program writes no
``engine.*`` spans."""
from benchmark import program_spans as P


def read(run):
    return P.per_step_ms(run, "in_wait_s")
