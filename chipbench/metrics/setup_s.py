"""Set-up seconds: from the start of the run to the start of the window —
building the service, compiling (or loading) its programs, the traffic's
prefill and warm-up, and the request plan."""


def read(ctx):
    return ctx.setup_s
