"""Share of the window's coded-segment encodes that ran as compiled
programs: 100 x the program's ``encodes_compiled`` counter over its
``encodes`` counter, summed over the window's request records
(``bench/spans.window``).  None when the log holds fewer records than the
window, when the window encoded nothing, or when no record carries the
``encodes_compiled`` counter: a program that does not count it."""
from bench.spans import window


def read(rec):
    records = window(rec)
    if records is None or not any("encodes_compiled" in r.counters
                                  for r in records):
        return None
    encodes = sum(r.count("encodes") for r in records)
    if encodes == 0:
        return None
    return 100.0 * sum(r.count("encodes_compiled") for r in records) / encodes
