"""The algorithm's work, one module per layer: what a roofline divides by.

Counts come from the cell's shapes and its seeded traffic through the
paper's load model (:mod:`bench.work.loads`), never from what the code that
runs does."""
