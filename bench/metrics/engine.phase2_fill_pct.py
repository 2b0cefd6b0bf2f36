"""Share of the leaf slots phase 2 evaluates that hold a real frontier
leaf, in %: the window's ``engine.phase2_slots{kind=frontier}`` over
``{kind=evaluated}`` (every row is padded to the pow2 class of the
batch's widest frontier)."""


def read(ctx):
    evaluated = ctx.counter("engine.phase2_slots{kind=evaluated}")
    if not evaluated:
        return None
    return 100.0 * ctx.counter("engine.phase2_slots{kind=frontier}") / evaluated
