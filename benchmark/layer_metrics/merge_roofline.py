"""The merge's share of its memory roofline: the bytes any implementation
must move (each fresh row read once and written once, 25 B a row: six
int32 columns and one bool, padding excluded; fresh rows are the window
delta of ``ops_ingested``) over the merge program's device time, as a
share of the chip's HBM bandwidth (benchmark/peaks.json).  Counted from
the work, not the code, so an O(batch) merge cannot read over 100%.
Moves write_ops_per_s."""
from benchmark.trace import program_time

ROW_BYTES = 25


def read(ctx):
    secs, n = program_time(ctx["trace"], "merge_checked")
    rows = ctx["counters"]["ops_ingested"]
    if not n or not secs or not rows:
        return None
    need = 2 * ROW_BYTES * rows / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / secs
