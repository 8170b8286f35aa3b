"""Idle share of the device in the traced window of a cell whose traffic
is of the sweep kind."""
from readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
