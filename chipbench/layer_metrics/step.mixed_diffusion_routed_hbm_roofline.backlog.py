"""A diffusion dispatch's share of its HBM roofline where it carries a prefill
chunk beside the lanes (the harness's kind ``mixed``): device trace x the
``kubeshare.engine.diffusion`` spans with ``chunk`` > 0 x the configuration's
``pass_min_bytes`` (the weights outside the experts once a dispatch, every
touched expert's three matrices once, the lanes' cached rows)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._diffusion import \
        mixed_diffusion_routed_hbm_roofline

    return mixed_diffusion_routed_hbm_roofline(run)
