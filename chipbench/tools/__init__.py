"""Tools the builder runs by hand on the chip; no cell reaches them."""
