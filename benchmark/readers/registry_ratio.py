"""Two counters of the engine's metrics registry, one over the other, by
their change over the window: ``offset + scale · Δnum ÷ Δden``.

``num`` and ``den`` are lists of metric names; every series of a name is
summed, whatever its labels (``rtfds_shard_chunks_total`` has one series
for local chunks and one for routed ones). ``scale=-100, offset=100``
turns a filled share into the per cent left empty. Nothing to read — a
program without the counter, or a window in which the denominator did
not move — is ``None``, and the metric is left out of the line."""


def _delta(ctx: dict, metrics):
    """Summed change of ``metrics`` over the window; None if the program
    has none of them."""
    before, after = ctx["registry_before"], ctx["registry_after"]
    total, found = 0.0, False
    for metric in metrics:
        rows = after.get(metric, {}).get("series", ())
        found = found or bool(rows)
        total += sum(r["value"] for r in rows) - sum(
            r["value"] for r in before.get(metric, {}).get("series", ()))
    return total if found else None


def read(ctx: dict, num, den, scale: float = 1.0, offset: float = 0.0):
    top, bottom = _delta(ctx, num), _delta(ctx, den)
    if top is None or not bottom or bottom < 0:
        return None
    return offset + scale * top / bottom
