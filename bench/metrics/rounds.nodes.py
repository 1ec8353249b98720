"""Mean rounds to the fixed point per node, as the program's
``NodeBatchResult.rounds`` reports them (fixed-point driver)."""


def read(ctx):
    rounds = ctx.counters.get("node_rounds")
    return sum(rounds) / len(rounds) if rounds else None
