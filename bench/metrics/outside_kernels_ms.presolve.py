"""Device milliseconds per presolve round outside the program's named
kernels: the window's busy time less theirs, over the window's rounds --
the round's XLA work and the fixed-point loop's own (fixed-point driver).
With ``round_kernel_ms.presolve`` it sums to the busy time per round."""
from bench import program_reads


def read(ctx):
    rounds = ctx.counters.get("presolve_rounds")
    if not rounds or ctx.trace is None:
        return None
    kernels = program_reads.kernel_seconds(ctx.trace)
    return None if kernels is None else 1e3 * (ctx.trace["busy_s"] - kernels) / sum(rounds)
