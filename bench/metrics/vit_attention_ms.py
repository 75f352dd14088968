"""vit_attention_ms: device time of the ViT's attention per round.

The traced window's ops whose op_name carries the program's
``vit.attention`` scope (each block's LayerNorm, qkv projection, scores,
softmax, weighted sum and output projection, forward and backward; they
sit inside ``pfels.local_train``, which counts them too), summed and
averaged over the chips, over the window's rounds. Read with
``scopes.py``'s decoding of the trace and ``tracing``'s leaf rule, so the
durations sum as the other readers'. Returns nothing where no op carries
the scope (a model without attention).
"""
import re

import scopes
import tracing

SCOPE = re.compile(r"\bvit\.attention\b")


def seconds(space, n_devices):
    """Device seconds of the scope's ops in the window, averaged over the
    chips."""
    w0, w1 = scopes.window(space)
    lines = scopes.device_lines(space, n_devices, "XLA Ops")
    total = 0
    for evs in lines.values():
        leaves = tracing._leaves([(op, max(a, w0), min(b, w1), text)
                                  for text, a, b, op in evs
                                  if b > w0 and a < w1])
        total += sum(b - a for op, a, b, _ in leaves if SCOPE.search(op))
    return total * 1e-9 / max(len(lines), 1)


def read(ctx):
    t = seconds(scopes.load(scopes.newest_trace()), ctx["chips"])
    return 1e3 * t / ctx["rounds"] if t > 0 else None
