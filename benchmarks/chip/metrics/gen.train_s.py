"""RSU augmented training per traced round (round/generate/train span: the
pool gather, its copy, 16 SGD steps and the loss readback)."""
SPAN = "round/generate/train"


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r[SPAN] for r in rounds if SPAN in r]
    return sum(hits) / len(rounds) if hits else None
