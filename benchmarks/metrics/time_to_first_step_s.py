"""Entry of the driver to the first completed step (data, prepare_data,
weights, compile or cache retrieval of the step)."""


def read(ctx):
    return ctx["counters"].get("time_to_first_step_s")
