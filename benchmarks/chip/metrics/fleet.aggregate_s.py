"""Fused fleet dispatch time per traced round (round/aggregate span: host
batch stacking, host-to-device copy, vmapped local SGD and eq.-4
aggregation)."""


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r["round/aggregate"] for r in rounds if "round/aggregate" in r]
    return sum(hits) / len(rounds) if hits else None
