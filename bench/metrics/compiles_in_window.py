"""compiles_in_window: backend compilations, persistent-cache loads included,
inside the traced calls, from the ``compiles`` the program attaches to its
vfl.run span (re-read from the profile). Should be 0. Moves protocol_s."""

from bench.metrics import _spans


def read(ctx):
    return _spans.compiles_in_window(ctx)
