"""Mean over the traced jobs that reached the target of the round at which
the in-graph stop fired (the first at or under the target)."""


def read(ctx):
    hit = [j.rounds for j in ctx.jobs if j.reached]
    return sum(hit) / len(hit) if hit else None
