"""device: the share of the traced window in which no operation ran on the
chip, in the stream cells."""


def read(run):
    return run.device.idle_percent()
