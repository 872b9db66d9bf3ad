"""device: the share of the traced window in which no operation ran, over
the chips of the mesh cell (busy time averaged over the chips that ran
any)."""


def read(run):
    return run.device.idle_percent()
