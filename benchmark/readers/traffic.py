"""A reading of the benchmark's own window source (the entry queue)."""


def read(ctx: dict, key: str):
    return ctx["queue_stats"].get(key)
