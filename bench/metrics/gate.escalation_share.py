"""Share of the window's answered requests whose S confidence fell below
theta, so that they escalated to the L tier (the scheduler's ``offloaded``
flag per request)."""


def read(run):
    recs = run.records.values()
    if not recs:
        return None
    return sum(bool(r["offloaded"]) for r in recs) / len(recs)
