"""Master-local time per request: the harness's span around each forward
minus the ``RunReport.wall_s`` of the executor runs inside it — encode,
decode, local convs, pools, skip adds and the head — averaged over the
window's requests."""


def read(rec):
    if not rec.requests or not rec.runs:
        return None
    master = sum(r.forward_s - r.run_wall_s for r in rec.requests)
    return master / len(rec.requests) * 1e3
