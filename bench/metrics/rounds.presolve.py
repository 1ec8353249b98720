"""Mean rounds to the fixed point per presolve call, as the program's
``PropagationResult.rounds`` reports them (fixed-point driver)."""


def read(ctx):
    rounds = ctx.counters.get("presolve_rounds")
    return sum(rounds) / len(rounds) if rounds else None
