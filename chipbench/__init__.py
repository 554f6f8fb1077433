"""chipbench: the on-chip benchmark of kubeshare-tpu (see README.md)."""
