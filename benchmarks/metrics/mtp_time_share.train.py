"""Device time of the multi-token-prediction module (the ops traced under
`hg_mtp`: the join, its own layer with both kernels, its norm; the second head
pass is the token loss's) over device busy time."""


def read(ctx):
    t = ctx["trace"]
    scope_s = (t or {}).get("scope_s")
    if not scope_s or not t["busy_s"] or not scope_s.get("hg_mtp"):
        return None
    return 100.0 * scope_s["hg_mtp"] / t["busy_s"]
