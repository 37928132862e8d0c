"""Engine launches per fleet round (``CrossTenantCoalescer``'s launch
count over the window, divided by tenant rounds per tenant)."""


def read(run):
    if not run.engine or not run.rounds:
        return None
    return run.launches / run.engine_rounds
