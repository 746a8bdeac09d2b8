"""On-chip benchmark of Deep RC: cells of a model configuration under one
traffic mix, run one at a time by ``bench/run.py``."""
