"""Source kind ``registry``: the program's own telemetry registry over
the window (``telemetry.snapshot_diff`` of two snapshots).

``{"kind": "registry", "series": <family>, "labels": {...},
"stat": "delta" | "mean", "scale": 1}`` — a counter's ``delta`` or a
histogram's ``mean`` (Δsum / Δcount), summed over every
series of the family whose labels include ``labels`` (series are keyed
``name{k="v",...}``, one per node id).  A family the program never
registered reads nothing; a counter that is registered and did not move
reads 0."""


def _matching(table: dict, spec: dict) -> list:
    name, labels = spec["series"], spec.get("labels", {})
    return [v for key, v in table.items()
            if (key == name or key.startswith(name + "{"))
            and all(f'{k}="{val}"' in key for k, val in labels.items())]


def read(spec: dict, ctx: dict):
    scale = spec.get("scale", 1)
    if spec["stat"] == "delta":
        if not _matching(ctx["registry_after"]["counters"], spec):
            return None
        return float(sum(_matching(ctx["registry"]["counters"], spec))) * scale
    moved = _matching(ctx["registry"]["histograms"], spec)
    count = sum(h["count"] for h in moved)
    if not count:
        return None
    if spec["stat"] == "mean":
        return sum(h["sum"] for h in moved) / count * scale
    raise ValueError(f"registry source: unknown stat {spec['stat']!r}")
