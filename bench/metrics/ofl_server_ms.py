"""ofl_server_ms: device milliseconds per epoch of the operations under the
epoch program's named scope ``ofl.server``, bare or wrapped by a transform,
averaged over the traced epochs and the chips; ops under nested scopes count
once. It includes the server network: its forward and backward in the
generator's adversarial term and in the KD sweep. Moves ofl_epoch_ms."""
from benchlib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ofl.server")
