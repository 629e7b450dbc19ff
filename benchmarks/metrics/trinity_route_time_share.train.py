"""Device time of routing around the AFMOE stack's experts (the ops traced
under the scopes `hg_router`, `hg_moe_dispatch` and `hg_moe_combine`) over
device busy time; the scopes' seconds are the lean driver's reading of the
traced span (`ctx["trace"]["scope_s"]`)."""

SCOPES = ("hg_router", "hg_moe_dispatch", "hg_moe_combine")


def read(ctx):
    t = ctx["trace"]
    scope_s = (t or {}).get("scope_s")
    if not scope_s or not t["busy_s"] or "layer_types" not in ctx["arch"] or not any(s in scope_s for s in SCOPES):
        return None
    return 100.0 * sum(scope_s.get(s, 0.0) for s in SCOPES) / t["busy_s"]
