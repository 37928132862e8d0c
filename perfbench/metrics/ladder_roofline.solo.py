"""Percent of the chip's int8 peak that the ladders reach: the counted
work of the window's exponentiations (``work.op_ops``) over the ladder
programs' device time.  Compute-bound, so compute alone is the roof."""


def read(run):
    if run.engine or run.trace is None or run.peaks is None \
            or not run.trace["ladder_s"]:
        return None
    return 100.0 * run.work_ops / run.trace["ladder_s"] \
        / run.peaks["int8_ops_per_s"]
