"""A statistic over the ``shard`` label of one metric of the engine's
registry: what a mesh's table-level series average away.

The sharded engine registers some series twice, table-level and with a
``shard`` label (``rtfds_feature_slots_occupied``,
``rtfds_feature_slots_reclaimed_total``), and some with the label alone
(``rtfds_keydir_shard_claim_rounds_total``). This reader takes only the
series of ``metric`` that carry the label (and ``labels``, if given), as
their change over the window, or as they stand at its end where ``over``
names the gauge each is divided by, series by series on the labels they
share (occupied ÷ capacity); ``by_shard`` first sums a shard's series
(both tables' rounds). Then one number: ``stat="max"``, ``"sum"``, or
``"max_over_mean"`` (1.0: every shard alike). ``per`` (metric names,
every series summed, as ``registry_ratio`` does) divides it by their
change over the window. Nothing to read — a program without the labelled
series, one shard, a mean or a divisor of zero — is ``None``, and the
metric is left out of the line."""

from benchmark.readers import registry_ratio


def _rows(snapshot: dict, metric: str, labels: dict):
    """``{labels as a sorted tuple: row}`` of the series of ``metric``
    that carry a ``shard`` label and every one of ``labels``."""
    return {tuple(sorted(row["labels"].items())): row
            for row in snapshot.get(metric, {}).get("series", ())
            if "shard" in row["labels"] and all(
                str(row["labels"].get(k)) == str(v)
                for k, v in labels.items())}


def read(ctx: dict, metric: str, stat: str, labels=None, over=None,
         by_shard: bool = False, per=None):
    before, after = ctx["registry_before"], ctx["registry_after"]
    rows = _rows(after, metric, labels or {})
    if over:
        caps = _rows(after, over, labels or {})
        values = {k: row["value"] / caps[k]["value"]
                  for k, row in rows.items() if caps.get(k, {}).get("value")}
    else:
        was = _rows(before, metric, labels or {})
        values = {k: row["value"] - (was[k]["value"] if k in was else 0.0)
                  for k, row in rows.items()}
    if by_shard:
        shards: dict = {}
        for k, v in values.items():
            shard = dict(k)["shard"]
            shards[shard] = shards.get(shard, 0.0) + v
        values = shards
    if len(values) < 2:
        return None
    got = list(values.values())
    if stat == "max_over_mean":
        mean = sum(got) / len(got)
        out = max(got) / mean if mean > 0 else None
    elif stat in ("max", "sum"):
        out = max(got) if stat == "max" else sum(got)
    else:
        raise ValueError(f"unknown stat {stat!r}")
    if per and out is not None:
        n = registry_ratio._delta(ctx, per)
        out = out / n if n and n > 0 else None
    return out
