"""bookkeeping_ms: device time of the round's other layers per round.

The traced window's ops under the program's ``pfels.cohort`` (round keys,
cohort draw, data and power-limit gathers, bank gather),
``pfels.channel``, ``pfels.beta``, ``pfels.ledger``, ``pfels.bank`` (lane
keys, bank scatter, residual update) and ``pfels.server`` scopes, summed,
over the window's rounds. Returns nothing where no op carries one.
"""
import scopes

SCOPES = ("pfels.cohort", "pfels.channel", "pfels.beta", "pfels.ledger",
          "pfels.bank", "pfels.server")


def read(ctx):
    return scopes.ms_per_round(ctx, SCOPES)
