"""``memory_stats()`` of the fullest chip after the window."""


def read(ctx: dict, key: str, scale: float = 1.0):
    value = ctx["device_memory"].get(key)
    return None if value is None else value * scale
