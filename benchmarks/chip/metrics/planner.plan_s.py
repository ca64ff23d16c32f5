"""SUBP2-4 planner time per traced round (round/plan span)."""


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r["round/plan"] for r in rounds if "round/plan" in r]
    return sum(hits) / len(rounds) if hits else None
