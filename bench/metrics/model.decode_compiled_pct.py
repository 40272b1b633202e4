"""Share of the window's coded-segment decodes that ran as compiled
programs: 100 x the program's ``decodes_compiled`` counter over its
``decodes`` counter, summed over the window's request records
(``bench/spans.window``).  None when the log holds fewer records than the
window, when the window decoded nothing, or when no record carries the
``decodes_compiled`` counter: a program that does not count it."""
from bench.spans import window


def read(rec):
    records = window(rec)
    if records is None or not any("decodes_compiled" in r.counters
                                  for r in records):
        return None
    decodes = sum(r.count("decodes") for r in records)
    if decodes == 0:
        return None
    return 100.0 * sum(r.count("decodes_compiled") for r in records) / decodes
