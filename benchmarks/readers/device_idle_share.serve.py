"""1 - the union of device-op intervals over the traced window."""


def read(ctx):
    return ctx["trace"].idle_share
