"""Traffic mixes: one JSON file of parameters per mix, one generator."""
