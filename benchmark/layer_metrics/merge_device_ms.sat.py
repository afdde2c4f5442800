"""Device milliseconds per execution of the op-log merge program
(``jit__merge_checked``: api/node.py _merge_batch, local drains and gossip
receives alike), from the trace.  Moves write_ops_per_s."""
from benchmark.trace import program_time


def read(ctx):
    secs, n = program_time(ctx["trace"], "merge_checked")
    return secs / n * 1e3 if n else None
