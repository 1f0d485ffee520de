"""A number the driver took from the program's own counters
(``result.facts[key]``), times ``scale``; ``None`` where the driver
found no such counter."""


def read(ctx, result, key, scale=1.0):
    value = result.facts.get(key)
    return None if value is None else scale * value
