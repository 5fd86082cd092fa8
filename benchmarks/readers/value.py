"""A number the driver already holds: ``values[key] * scale``."""


def read(obs, *, key: str, scale: float = 1.0):
    value = obs["values"].get(key)
    return None if value is None else value * scale
