"""A key of the harness's `[steadiness]` line: gaps between the window's
acknowledgements, its sink writes and its polls, by the host's clock."""


def read(ctx: dict, key: str):
    return ctx["steadiness"].get(key)
