"""Host layer: seconds `lgb.Dataset` construction (binning) took, from the
benchmark's own span around it.  Moves setup_s."""


def read(ctx):
    return ctx["spans"].get("bin_s")
