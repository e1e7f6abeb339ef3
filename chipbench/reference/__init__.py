"""Plain float32 references, one module per model family. They import
nothing of the program under test; each also makes the benchmark's
seeded weights in the layout the program serves."""
