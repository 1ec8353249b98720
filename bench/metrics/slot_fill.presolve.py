"""Share of the slots a presolve round runs over that hold a nonzero, in
percent: the instance's nonzeros over the slots of the tile streams the
round runs (packed and long-row chunk rows, block-ELL tiles or slab
copies), from the ``nnz`` and ``slots`` of the window's ``prop.presolve``
spans (round kernels).  A program whose spans carry no ``slots`` gives
``None``."""
from bench import program_reads


def read(ctx):
    rounds = ctx.counters.get("presolve_rounds")
    found = program_reads.window_calls("prop.presolve", len(rounds or ()))
    if found is None:
        return None
    calls = found[0]
    if any("slots" not in s.attrs or "nnz" not in s.attrs for s in calls):
        return None
    return 100.0 * sum(s.attrs["nnz"] for s in calls) / sum(s.attrs["slots"] for s in calls)
