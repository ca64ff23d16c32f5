"""Fused fleet dispatch per traced round (round/aggregate/step span: the
vmapped local SGD and eq.-4 aggregation up to the loss readback)."""
SPAN = "round/aggregate/step"


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r[SPAN] for r in rounds if SPAN in r]
    return sum(hits) / len(rounds) if hits else None
