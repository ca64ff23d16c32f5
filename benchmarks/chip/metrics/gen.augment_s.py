"""RSU augmented-model time per traced round: round/generate less its
round/generate/sample child (label schedule, pool append, 16 SGD steps)."""


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r["round/generate"] - r.get("round/generate/sample", 0.0)
            for r in rounds if "round/generate" in r]
    return sum(hits) / len(rounds) if hits else None
