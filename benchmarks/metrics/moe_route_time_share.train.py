"""Device time of routing around the experts (the ops traced under the scopes
`hg_router`, `hg_moe_dispatch` and `hg_moe_combine`: float32 scores, top-k,
the row layout, the gather into expert rows and the weighted sum back) over
device busy time. The scopes' seconds are `drive_train_tokens_lean.py`'s
reading of the traced span."""

SCOPES = ("hg_router", "hg_moe_dispatch", "hg_moe_combine")


def read(ctx):
    t = ctx["trace"]
    scope_s = (t or {}).get("scope_s")
    if not scope_s or not t["busy_s"] or not any(s in scope_s for s in SCOPES):
        return None
    return 100.0 * sum(scope_s.get(s, 0.0) for s in SCOPES) / t["busy_s"]
