"""Model families: program configuration, weights, FLOPs, plain reference."""
