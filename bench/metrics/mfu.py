"""mfu: the round program's share of the chip's bf16 peak.

Model FLOPs of local training (3 x the forward of r * tau * B samples per
round, counted by ``flops.py`` from the configuration's forward) times the
rounds the traced window completed, over the window's seconds and over
chips x peak FLOP/s. Rounds, not device time: idle time counts against it.
"""


def read(ctx):
    per_round = ctx["train_flops_per_sample"] * ctx["samples_per_round"]
    rate = per_round * ctx["rounds"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
