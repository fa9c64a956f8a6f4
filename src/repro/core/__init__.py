"""The simulator: trace sources, the timing engine, the suite, DSE and search."""
