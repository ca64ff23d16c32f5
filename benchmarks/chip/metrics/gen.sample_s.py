"""Diffusion sampling time per traced round (round/generate/sample)."""


def read(ctx):
    rounds = ctx["red"]["rounds"]
    hits = [r["round/generate/sample"] for r in rounds
            if "round/generate/sample" in r]
    return sum(hits) / len(rounds) if hits else None
