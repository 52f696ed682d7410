"""Source kind ``driver``: a value the cell's driver returned from its
window under ``values`` — ``{"kind": "driver", "key": ..., "scale": 1}``."""


def read(spec: dict, ctx: dict):
    value = ctx["values"].get(spec["key"])
    return None if value is None else float(value) * spec.get("scale", 1)
