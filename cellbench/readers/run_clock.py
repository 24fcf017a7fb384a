"""Metrics the benchmark's own process clocks (set-up time)."""


def read(ctx: dict, params: dict):
    return ctx["clock"].get(params["field"])
