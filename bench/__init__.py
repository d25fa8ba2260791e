"""The on-chip benchmark of the design service: harness, traffic, models'
reference, trace reduction and per-layer metric readers."""
