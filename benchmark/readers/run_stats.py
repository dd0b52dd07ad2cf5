"""A key of the dict ``engine.run()`` returned for the window."""


def read(ctx: dict, key: str):
    return ctx["run_stats"].get(key)
