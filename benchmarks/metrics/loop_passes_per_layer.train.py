"""Applications of each held layer a step in a looped stack (program
counters `count:loop_layer_applications` over `count:loop_layers_held`,
summed over the window's steps): the passes the step runs over one set of
weights, 4.0 where every pass runs; a program without the counters reads
nothing."""

import decoder_reads


def read(ctx):
    runs, held = decoder_reads.counter(ctx, "loop_layer_applications"), decoder_reads.counter(ctx, "loop_layers_held")
    return runs / held if runs is not None and held else None
