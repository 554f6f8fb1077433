"""What a row of a live lane's context costs a pool by layer kind: the pages
in use of the full kind x a full page's bytes + those of the window kind x a
window page's, over the live lanes' context rows (the
``kubeshare.engine.kv_kinds`` spans of the traced tail).  Every layer's row
(16,384 B at ``smallthinker-21ba3b-instruct``) where nothing is handed
back."""

LAYER = "serving scheduler"
UNIT = "bytes"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._kinds import pool_bytes_per_context_row

    return pool_bytes_per_context_row(run)
