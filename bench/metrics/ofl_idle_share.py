"""ofl_idle_share: the share of the traced window in which no operation ran
on the chip (1 minus the union of device op intervals). Moves ofl_epoch_ms."""
from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx, "ofl")
