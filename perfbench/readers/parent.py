"""A number the load generator took on its own clock (`run.py`)."""


def read(spec: dict, ctx: dict):
    return ctx["parent"].get(spec["key"])
