"""support_ms: device time of support selection per round.

The traced window's ops under the program's ``pfels.support`` scope (the
compressor's support draw, rand_k's permutation sort of d among them,
and the schedule's live-slot mask), summed, over the window's rounds:
the layer ``support_sort_ms`` measures by opcode, read from inside it.
Returns nothing where no op carries the scope.
"""
import scopes

SCOPES = ("pfels.support",)


def read(ctx):
    return scopes.ms_per_round(ctx, SCOPES)
