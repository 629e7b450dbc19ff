"""Device time of Mosaic (Pallas) custom calls over device busy time."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["mosaic_s"] or not t["busy_s"]:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
