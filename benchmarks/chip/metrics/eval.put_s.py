"""Host-to-device copy of the test set per traced round (round/eval/put
span: images and labels, fenced)."""
SPAN = "round/eval/put"


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r[SPAN] for r in rounds if SPAN in r]
    return sum(hits) / len(rounds) if hits else None
