"""Share of a decoder stack's token-head row chunks whose gradient the
forward scan formed (program counters `count:head_chunks_grad_in_forward`
over `count:head_chunks`, summed over the window's steps and the head's
passes): 100 where the chunked cross-entropy forms `softmax - onehot` and
both gradient products in its one forward scan and the backward only scales
them, 0 in evaluation; a program without the counters reads nothing."""

import decoder_reads


def read(ctx):
    formed = decoder_reads.counter(ctx, "head_chunks_grad_in_forward")
    chunks = decoder_reads.counter(ctx, "head_chunks")
    return 100.0 * formed / chunks if formed is not None and chunks else None
