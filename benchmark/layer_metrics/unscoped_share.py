"""Percent of a step's device time in instructions whose name stack holds no
scope of the LM's vocabulary (``scope_reduce.LM_SCOPES``): the guard that the
names stay whole. 100 on a program that enters no scope. Layer: device."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.unscoped_share(scope_reduce.of(ctx))
