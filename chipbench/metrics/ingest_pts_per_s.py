"""Points committed per second: the rows of the commits that completed
after the commit that opened the window, up to and including the first
commit completion ``--seconds`` later, over that time (host clock)."""


def read(ctx):
    w = ctx.window
    if ctx.cell.traffic["kind"] != "ingest" or not w.get("seconds"):
        return None
    return w["rows"] / w["seconds"]
