"""The benchmark's yardstick: traffic generation, FLOP and byte counts,
the table of peaks, the plain float32 reference, the trace reduction.
Nothing here imports the program under test."""
