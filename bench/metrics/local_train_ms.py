"""local_train_ms: device time of local training per round.

The traced window's ops that carry the program's ``pfels.local_train``
scope (the r clients' vmapped local steps, forward and backward, the
model update and its ravel, the error-feedback add, the round's loss and
update-norm metrics), summed, over the window's rounds. ``scopes.py``
reads each op's scope from the trace. Returns nothing where no op
carries it.
"""
import scopes

SCOPES = ("pfels.local_train",)


def read(ctx):
    return scopes.ms_per_round(ctx, SCOPES)
