"""Host round-loop time per traced round: the round/fleet, round/select,
round/local_sgd (host batch gathers) and round/world_step spans."""
SPANS = ("round/fleet", "round/select", "round/local_sgd", "round/world_step")


def read(ctx):
    rounds = ctx["red"]["rounds"]
    if not rounds:
        return None
    return sum(sum(r.get(s, 0.0) for s in SPANS) for r in rounds) / len(rounds)
