"""idle_share: the share of the traced window in which no operation ran
on the device, averaged over the chips used."""


def read(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
