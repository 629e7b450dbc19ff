"""Device time of a looped stack's exits' loss (the ops traced under
`hg_token_loss`: the head over every pass's exit, the exit distribution, the
expected loss and its entropy term, forward and backward) over device busy
time."""


def read(ctx):
    t = ctx["trace"]
    scope_s = (t or {}).get("scope_s")
    if not scope_s or not t["busy_s"] or not scope_s.get("hg_token_loss"):
        return None
    return 100.0 * scope_s["hg_token_loss"] / t["busy_s"]
