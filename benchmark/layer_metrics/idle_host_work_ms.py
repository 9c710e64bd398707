"""Device: idle time of the device (gaps of ``XLA Ops``, device 0) while the
host did its own work inside a step: the gaps intersected with ``engine.step``
minus the two wait spans, per ``engine.step`` span that holds an
``engine.dispatch`` in the trace. Left out without a device plane, or where
the program writes no ``engine.*`` spans."""
from benchmark import program_spans as P


def read(run):
    return P.per_step_ms(run, "host_work_s")
