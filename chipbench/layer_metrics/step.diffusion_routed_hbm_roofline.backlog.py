"""A diffusion dispatch's share of its HBM roofline: device trace x the
``kubeshare.engine.diffusion`` spans' ``touched`` and ``kv_rows`` x the
configuration's ``pass_min_bytes`` (the weights outside the experts once a
pass, every touched expert's three matrices, the lanes' cached rows)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._diffusion import \
        diffusion_routed_hbm_roofline

    return diffusion_routed_hbm_roofline(run)
