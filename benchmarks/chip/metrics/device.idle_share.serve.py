"""Share of the traced window in which no op ran on the chip."""


def read(run):
    return 100.0 * run.trace.idle_share()
