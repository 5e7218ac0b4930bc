"""GridPilot chip benchmark: harness, configurations, traffic, metrics."""
