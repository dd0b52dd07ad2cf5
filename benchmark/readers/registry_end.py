"""A gauge of the engine's metrics registry as it stands at the window's
end: the series of ``metric`` that carries ``labels`` (the first, where
none are given). Nothing to read — a program without the gauge — is
``None``, and the metric is left out of the line."""

from benchmark.readers import registry


def read(ctx: dict, metric: str, labels=None):
    row = registry._series(ctx["registry_after"], metric, labels or {})
    return None if row is None else row["value"]
