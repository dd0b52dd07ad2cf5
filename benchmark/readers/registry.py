"""A series of the engine's metrics registry, as its change over the
window: ``stat="delta"`` (counters; several ``metrics`` are summed) or
``stat="mean_ms"`` (a histogram's sum over its count, in milliseconds)."""


def _series(snapshot: dict, metric: str, labels: dict):
    for row in snapshot.get(metric, {}).get("series", ()):
        if all(str(row["labels"].get(k)) == str(v) for k, v in labels.items()):
            return row
    return None


def read(ctx: dict, metrics, stat: str, labels=None):
    before, after = ctx["registry_before"], ctx["registry_after"]
    labels = labels or {}
    total, found = 0.0, False
    for metric in metrics:
        a, b = _series(after, metric, labels), _series(before, metric, labels)
        if a is None:
            continue
        found = True
        if stat == "delta":
            total += a["value"] - (b["value"] if b else 0.0)
        elif stat == "mean_ms":
            n = a["count"] - (b["count"] if b else 0)
            if n <= 0:
                return None
            return (a["sum"] - (b["sum"] if b else 0.0)) / n * 1e3
        else:
            raise ValueError(f"unknown stat {stat!r}")
    # a counter nobody has touched does not exist yet: nothing happened
    return total if found or stat == "delta" else None
