"""Device: idle time of the device (gaps of ``XLA Ops``, device 0) outside
every ``engine.step`` and outside ``bench.sleep``, per ``engine.step`` span
that holds an ``engine.dispatch`` in the trace: the load generator's own
bookkeeping between steps, which is not the program's to mend. Left out
without a device plane, or where the program writes no ``engine.*`` spans."""
from benchmark import program_spans as P


def read(run):
    return P.per_step_ms(run, "outside_s")
