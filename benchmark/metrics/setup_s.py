"""Process start to the window's start: import, rows from the seed, binning,
compile or cache load, and the warm-up chunk (end-to-end)."""


def read(ctx):
    return ctx["setup_s"]
