"""Tokens the decode steps produced per step over the window: ``decodez()``
deltas, (tokens - prefills) / steps — a prefill samples its request's first
token, every other token comes from a step."""


def read(ctx):
    z = ctx.get("decodez")
    if not z or z["steps"] <= 0:
        return None
    return (z["tokens"] - z["prefills"]) / z["steps"]
