"""RSU pool append per traced round (round/generate/pool span: the
concatenation of the sampled images and labels onto the pool)."""
SPAN = "round/generate/pool"


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r[SPAN] for r in rounds if SPAN in r]
    return sum(hits) / len(rounds) if hits else None
