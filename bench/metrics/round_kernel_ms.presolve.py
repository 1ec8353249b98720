"""Device milliseconds per presolve round in the program's named Mosaic
kernels (``KERNEL_NAMES``): their time among the traced window's listed
device ops over the window's rounds (round kernels)."""
from bench import program_reads


def read(ctx):
    rounds = ctx.counters.get("presolve_rounds")
    if not rounds or ctx.trace is None:
        return None
    kernels = program_reads.kernel_seconds(ctx.trace)
    return None if kernels is None else 1e3 * kernels / sum(rounds)
