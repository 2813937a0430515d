"""Explicit blocking device->host reads per round of the event loop: the
program's ``host_syncs`` counter over its ``event_rounds`` counter
(``repro.core.executor.STATS``).  None from a program without these
counters.

Two limits, so 2.0 is not read as every point where a round waits:

* It counts over the whole process, the warm-up job and the traced job,
  where the other gap metrics count the traced window (rounds 11-20).  Each
  job stops at a certificate, so the ratio is the window's too, but a
  change confined to the window would be diluted.
* It counts only the reads the program makes on purpose: a round's reply
  ``nnz`` and each ``float`` of a streamed certificate, so it reads
  1 + 5 / eval_every.  The round's longest wait is elsewhere: the split's
  first eager slice blocks until the worker program ends, which no counter
  sees (``bench/profile_cell.py`` reads it from the trace as
  ``split_wait_s``).
"""


def read(ctx):
    from repro.core.executor import STATS

    rounds = STATS.get("event_rounds")
    if not rounds or "host_syncs" not in STATS:
        return None
    return STATS["host_syncs"] / rounds
