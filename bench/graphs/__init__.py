"""Graph generators, one module per name: ``generate(cfg, seed) -> Graph``."""
