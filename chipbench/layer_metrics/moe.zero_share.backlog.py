"""Router choices that fell on a zero-compute (identity) expert, of all choices
(held + zero + absent), over the ``kubeshare.engine.routing`` spans of the
traced tail.  A property of the mathematics (a third of the outputs are
identity experts: about 33% with seeded weights and no bias): a change that
moves it changed what is computed."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._routing import zero_share

    return zero_share(run)
