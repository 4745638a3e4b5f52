"""The chip benchmark: harness, traffic generator, counts, trace
reduction, configurations, traffic mixes and metric readers."""
