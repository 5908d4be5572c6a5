"""ofl_gen_net_ms: device milliseconds per epoch of the operations under the
epoch program's named scope ``ofl.gen.net``, bare or wrapped by a transform,
averaged over the traced epochs and the chips; ops under nested scopes count
once. It includes the generator network: its forward in the T_G steps, in
the loss evaluation and for the fresh batch, and its weight-gradient
backward. Moves ofl_epoch_ms."""
from benchlib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ofl.gen.net")
