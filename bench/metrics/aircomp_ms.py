"""aircomp_ms: device time of the aggregation per round.

The traced window's ops under the program's ``pfels.aircomp`` scope
(whichever aggregation runs: the support scatters into the dense mask
and noise columns, the fused transmit kernel and the server unscale, or
the unfused, sharded or digital paths), summed, over the window's
rounds. Returns nothing where no op carries the scope.
"""
import scopes

SCOPES = ("pfels.aircomp",)


def read(ctx):
    return scopes.ms_per_round(ctx, SCOPES)
