"""serve_idle_share: the share of the traced window in which no operation ran
on the chip (1 minus the union of device op intervals): waits for arrivals,
the scheduler's host work between programs, and the drain. Moves
serve_tpot_ms."""
from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx, "serve")
