"""Share of the traced window in which no op ran on the device, averaged
over the chips, in percent. Device trace."""

from bench import readers


def read(run):
    return readers.idle_share(run)
