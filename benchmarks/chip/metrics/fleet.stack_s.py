"""Host stacking time per traced round (round/aggregate/stack span: np.stack
of the K vehicles' batches, padding to the fleet bucket, the weights)."""
SPAN = "round/aggregate/stack"


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r[SPAN] for r in rounds if SPAN in r]
    return sum(hits) / len(rounds) if hits else None
