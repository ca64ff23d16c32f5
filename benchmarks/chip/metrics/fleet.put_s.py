"""Host-to-device copy of the fleet batch per traced round
(round/aggregate/put span: images, labels and weights, fenced)."""
SPAN = "round/aggregate/put"


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r[SPAN] for r in rounds if SPAN in r]
    return sum(hits) / len(rounds) if hits else None
