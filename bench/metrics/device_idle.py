"""Device: the share of the traced window in which no operation ran on
the chip, in %."""


def read(run):
    if not run.trace or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
