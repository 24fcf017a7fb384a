"""Metrics taken on the client's side of the HTTP stream."""


def read(ctx: dict, params: dict):
    return ctx["client"].get(params["field"])
