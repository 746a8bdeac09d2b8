"""The benchmark's own machinery: manifest lookup, traffic generation,
weights, cell modules, trace reduction, work counts and the result line.
Nothing here is specific to one cell; cells, traffic mixes and per-layer
metrics are data and reader files found by name."""
