"""transmit_roofline: the fused transmit kernel's share of its roofline.

Over every execution of a compiled Pallas kernel (``tpu_custom_call``) in
the traced window: the least time the chip could take, the larger of its
FLOPs over peak FLOP/s and its bytes over peak bytes/s, summed, over the
kernels' summed device time. Bytes are the sizes of the kernel's operands
and results as the trace's instruction text gives them, so a change of
dtype counts; FLOPs are 6 per element of its largest operand, the (r, d)
update batch (mask, scale and accumulate the signal; square and
accumulate the energy). At r = 32 the bytes bound it by far. Returns
nothing where no kernel ran.
"""


def read(ctx):
    pk = ctx["peaks"]
    kern = [op for op in ctx["ops"] if op["kind"] == "kernel"]
    spent = sum(op["seconds"] for op in kern)
    if spent <= 0:
        return None
    least = sum(op["count"] * max(6 * op["elements"] / pk["bf16_flops"],
                                  op["bytes"] / pk["hbm_bytes_per_s"])
                for op in kern)
    return 100.0 * least / spent
