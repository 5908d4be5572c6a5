"""ofl_dhs_ms: device milliseconds per epoch of the operations under the epoch
program's named scope ``ofl.dhs``, bare or wrapped by a transform, averaged
over the traced epochs and the chips; ops under nested scopes count once. It
includes the hard-sample search (Eq. 10) in EE and in every KD step: a bank
forward, the input gradient and the perturbation. Its bank work counts in
ofl_bank_ms too. Moves ofl_epoch_ms."""
from benchlib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ofl.dhs")
