"""support_sort_ms: device time of the trace's sort operations per round.

rand_k draws its support as the first k of a permutation of d, which JAX
computes by sorting random keys; nothing else in the round sorts. Returns
nothing where the trace holds no sort.
"""


def read(ctx):
    t = sum(op["seconds"] for op in ctx["ops"] if op["kind"] == "sort")
    return 1e3 * t / ctx["rounds"] if t > 0 else None
