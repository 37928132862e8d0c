"""Percent of the window's ladder rows that ran on one chip only, from the
program's own per-round counts (``CoalesceQueue.layout``: ladder rows,
and those of launches that ran on one chip), summed over the rounds that
ended inside the window.  ``None`` under the engine, for a program that
keeps no such counts, or without a ladder row in the window."""


def read(run):
    if run.engine:
        return None
    rows = one_chip = 0
    for dep in run.deployments:
        per_round = getattr(dep.rt.cq, "layout", None)
        if per_round is None:
            return None
        for t, at in dep.round_ends:
            if at > run.window.start:
                n = per_round.get(t, {})
                rows += n.get("rows", 0)
                one_chip += n.get("one_chip", 0)
    return 100.0 * one_chip / rows if rows else None
