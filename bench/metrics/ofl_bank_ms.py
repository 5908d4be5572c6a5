"""ofl_bank_ms: device milliseconds per epoch of the operations under the epoch
program's named scope ``ofl.bank``, bare or wrapped by a transform, averaged
over the traced epochs and the chips; ops under nested scopes count once. It
includes the client bank's K clients (every architecture group,
ofl.bank.g<i>), forward and, through autodiff, the input-gradient backward,
in every phase: the generator's steps and loss, EE, DHS and KD. Moves
ofl_epoch_ms."""
from benchlib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ofl.bank")
