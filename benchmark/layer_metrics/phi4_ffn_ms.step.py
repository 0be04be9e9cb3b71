"""Device milliseconds a local step in the feed-forwards: every row under
`step/model` that is under none of the family's mixers' scopes, a norm, the
embedding or the loss (`scope_reduce_phi4flash.NOT_FFN`) -- the four SwiGLUs'
three products each and the gate between them, with the residual stream's
additions --, forward, recomputation and backward: by FLOPs the part that
sets the pace (58 % of a step)."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.feed_forward, cell["steps_per_round"])
