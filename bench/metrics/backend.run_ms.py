"""Mean wall time of one coded executor run (``RunReport.wall_s``):
dispatch to the pool's workers until the k-th arrival."""


def read(rec):
    if not rec.runs:
        return None
    return sum(wall for _n, _k, wall in rec.runs) / len(rec.runs) * 1e3
